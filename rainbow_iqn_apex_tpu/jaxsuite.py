"""Benchmark suite over the pure-JAX game family (envs/device_games.py).

Role: the runnable counterpart of the Atari-57 harness (atari57.py).  The
reference's headline benchmark needs ALE + ROMs, absent in this sandbox
(SURVEY.md §7); this suite gives the framework a benchmark it can actually
execute anywhere: same sweep driver shape, same CSV/aggregate outputs, same
normalisation math — but with baselines that are MEASURED, not recalled:

- random baseline: the measured mean return of a uniform-random policy;
- scripted reference: the measured mean return of a hand-written competent
  policy (state-based, defined per game where one is sensible).

normalized = (score - random) / (scripted - random) — "1.0 plays like the
script, 0.0 plays like noise" — so nothing in the aggregate rests on an
unverifiable constant (contrast atari57.HUMAN_WORLD_RECORDS, which stays
RECON-gated).  Baselines are computed on demand by vmapped device rollouts
of the same in-graph step the trainers use.
"""

from __future__ import annotations

import json
import os
from statistics import median as _median
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from rainbow_iqn_apex_tpu.envs.device_games import (
    GAMES,
    build_rollout,
    make_device_game,
    tick_budget,
)

JAXSUITE = sorted(GAMES)


# ---------------------------------------------------------------- policies


def _p_random(game):
    def policy(state, key):
        return jax.random.randint(key, (), 0, game.num_actions, jnp.int32)

    return policy


def _p_catch(game):
    def policy(state, key):
        d = state.ball_c - state.paddle
        return jnp.where(d == 0, 0, jnp.where(d > 0, 2, 1)).astype(jnp.int32)

    return policy


def _p_breakout(game):
    from rainbow_iqn_apex_tpu.envs.device_games import G

    HORIZON = 24  # covers any ascent/descent cycle through the brick wall

    def policy(state, key):
        # trajectory-aware: roll the game's own ball dynamics (side
        # reflection with its one-tick wall dwell, top bounce, brick bounces
        # against a local copy of the wall) forward until the ball first
        # reaches the paddle plane, and head for that column the whole time
        # — chasing the ball's current column drags the paddle out of
        # position for the mirrored descent (measured: ~3 bricks/life vs
        # ~20+ with the trajectory target).  Paddle speed (1 cell/tick) is
        # the remaining, intended limitation of this ceiling.
        def body(_, carry):
            r, c, dr, dc, bricks, landed, land_c = carry
            nc = c + dc
            flip = (nc < 0) | (nc > G - 1)
            dc2 = jnp.where(flip, -dc, dc)
            nc = jnp.clip(nc, 0, G - 1)
            nr = r + dr
            dr2 = jnp.where(nr < 0, jnp.int32(1), dr)
            nr = jnp.where(nr < 0, jnp.int32(1), nr)
            nr_idx = jnp.clip(nr, 0, G - 1)
            hit = bricks[nr_idx, nc]
            bricks = bricks.at[nr_idx, nc].set(
                jnp.where(hit, False, bricks[nr_idx, nc])
            )
            dr2 = jnp.where(hit, -dr2, dr2)
            nr = jnp.where(hit, r, nr)
            at_bottom = nr >= G - 1
            land_c = jnp.where(at_bottom & ~landed, nc, land_c)
            new_landed = landed | at_bottom
            keep = landed
            return (
                jnp.where(keep, r, nr), jnp.where(keep, c, nc),
                jnp.where(keep, dr, dr2), jnp.where(keep, dc, dc2),
                bricks, new_landed, land_c,
            )

        init = (state.ball_r, state.ball_c, state.dr, state.dc,
                state.bricks, jnp.bool_(False), state.ball_c)
        *_, landed, land_c = jax.lax.fori_loop(0, HORIZON, body, init)
        target = jnp.where(landed, land_c, state.ball_c)
        d = target - state.paddle
        return jnp.where(d == 0, 0, jnp.where(d > 0, 2, 1)).astype(jnp.int32)

    return policy


def _p_freeway(game):
    from rainbow_iqn_apex_tpu.envs.device_games import G

    COL = game.CHICKEN_COL

    def _danger(state, row):
        """Will the lane at `row` (chicken rows 1..8) be dangerous next
        tick?  A car within 2 cells and approaching, or parked on the
        crossing column.  Lane dynamics come from the game's
        `_lane_dynamics(state)` hook, NOT the class constants, so the script
        stays a valid ceiling for '@var' levels whose speeds/dirs ride in
        the state."""
        lane = row - 1
        on_road = (lane >= 0) & (lane < 8)
        li = jnp.clip(lane, 0, 7)
        car = state.cars[li]
        _speeds, dirs = game._lane_dynamics(state)
        gap = car - COL  # signed distance to the crossing column
        approaching = jnp.sign(-gap) == jnp.sign(dirs[li])
        near = jnp.abs(gap) <= 2
        return on_road & ((gap == 0) | (near & approaching))

    def policy(state, key):
        # gap-aware crossing: step up when the lane above is clear; if the
        # current lane is about to be hit, prefer up, else retreat; never
        # idle in traffic for no reason
        up_ok = ~_danger(state, state.chicken - 1)
        here_bad = _danger(state, state.chicken)
        down_ok = ~_danger(state, state.chicken + 1)
        a = jnp.where(
            up_ok, 1,
            jnp.where(here_bad & down_ok, 2, 0),
        )
        return a.astype(jnp.int32)

    return policy


def _p_asterix(game):
    from rainbow_iqn_apex_tpu.envs.device_games import G

    def policy(state, key):
        lanes = jnp.arange(8)
        rows = lanes + 1
        enemy = state.active & ~state.gold
        gold = state.active & state.gold
        gap = state.col - state.pc  # per-lane signed distance to player col
        approaching = jnp.sign(-gap) == jnp.sign(state.dirn)
        threat = enemy & (jnp.abs(gap) <= 2) & ((gap == 0) | approaching)

        here = rows == state.pr
        above = rows == state.pr - 1
        below = rows == state.pr + 1
        in_danger = (threat & here).any()
        up_ok = (state.pr > 1) & ~(threat & above).any()
        down_ok = (state.pr < 8) & ~(threat & below).any()

        # nearest gold lane (inactive lanes pushed to +inf distance)
        gdist = jnp.where(gold, jnp.abs(rows - state.pr) * G + jnp.abs(gap),
                          jnp.int32(10 * G))
        gi = jnp.argmin(gdist)
        has_gold = gold.any()
        g_row, g_col = rows[gi], state.col[gi]
        to_gold = jnp.where(
            g_row < state.pr, 3,
            jnp.where(
                g_row > state.pr, 4,
                jnp.where(g_col < state.pc, 1,
                          jnp.where(g_col > state.pc, 2, 0)),
            ),
        )
        chase = jnp.where(has_gold, to_gold, 0)

        # dodge enemies first (vertical escape, sideways as a last resort),
        # otherwise chase the nearest gold
        flee = jnp.where(up_ok, 3, jnp.where(down_ok, 4, jnp.where(
            (threat & here & (gap >= 0)).any(), 1, 2)))
        return jnp.where(in_danger, flee, chase).astype(jnp.int32)

    return policy


def _p_invaders(game):
    from rainbow_iqn_apex_tpu.envs.device_games import G

    def policy(state, key):
        # dodge a falling bomb on our column, else line up with the nearest
        # alien column and fire
        bomb_close = (state.bomb_r >= 0) & (state.bomb_r >= G - 4)
        dodge = bomb_close & (state.bomb_c == state.pc)
        dodge_dir = jnp.where(state.pc > 0, 1, 2)

        cols_occ = state.aliens.any(axis=0)
        cdist = jnp.where(cols_occ, jnp.abs(jnp.arange(G) - state.pc),
                          jnp.int32(10 * G))
        tgt = jnp.argmin(cdist)
        aligned = cols_occ[state.pc]
        can_fire = state.shot_r < 0
        seek = jnp.where(
            aligned, jnp.where(can_fire, 3, 0),
            jnp.where(tgt < state.pc, 1, 2),
        )
        return jnp.where(dodge, dodge_dir, seek).astype(jnp.int32)

    return policy


# game -> scripted policy builder (every game has a competent ceiling so
# "1.0 = plays like the script" is meaningful suite-wide)
SCRIPTED: Dict[str, Optional[Callable]] = {
    "catch": _p_catch,
    "breakout": _p_breakout,
    "freeway": _p_freeway,
    "asterix": _p_asterix,
    "invaders": _p_invaders,
}


# ---------------------------------------------------------------- rollouts


def rollout_returns(name: str, policy_builder, episodes: int = 64,
                    seed: int = 0, max_ticks: Optional[int] = None) -> np.ndarray:
    """FIRST-episode returns of `policy` on `episodes` parallel lanes via the
    shared rollout core (envs/device_games.build_rollout) — same episode
    accounting as the trainers' in-graph eval, including capped-return
    semantics: a lane still mid-episode at the tick budget scores its
    partial return, so long-surviving policies (breakout rallies) are
    counted, never censored."""
    game = make_device_game(name)
    policy = policy_builder(game)
    T = max_ticks or tick_budget(name)

    def action_fn(aux, states, stack, key):
        return jax.vmap(policy)(states, jax.random.split(key, episodes))

    run = build_rollout(game, action_fn, episodes, T, history=0)
    return np.asarray(run(None, jax.random.PRNGKey(seed)))


def measure_baselines(name: str, episodes: int = 64, seed: int = 0) -> Dict:
    """Measured {random, scripted?} mean returns for one game (capped-return
    semantics — every lane contributes; the emptiness guards below are pure
    defence-in-depth)."""
    out: Dict[str, float] = {}
    rnd = rollout_returns(name, _p_random, episodes, seed)
    if len(rnd):
        out["random"] = float(np.mean(rnd))
    builder = SCRIPTED.get(name)
    if builder is not None:
        scr = rollout_returns(name, builder, episodes, seed + 1)
        if len(scr):
            out["scripted"] = float(np.mean(scr))
    return out


def normalized_score(raw: float, baselines: Dict) -> Optional[float]:
    """(raw - random) / (scripted - random); None without a scripted ceiling
    meaningfully above random (or with non-finite baselines)."""
    rnd = baselines.get("random")
    scr = baselines.get("scripted")
    if rnd is None or scr is None:
        return None
    if not (np.isfinite(rnd) and np.isfinite(scr)) or scr <= rnd + 1e-6:
        return None
    return (raw - rnd) / (scr - rnd)


def aggregate(per_game_raw: Dict[str, float],
              baselines: Dict[str, Dict]) -> Dict[str, object]:
    """Suite aggregate: counts, median/mean script-normalized scores, the
    per-game normalized map and the below-0.2 floor count (mixed value
    types — treat as a JSON object, not a float map)."""
    norm = {
        g: n
        for g, s in per_game_raw.items()
        if (n := normalized_score(s, baselines.get(g, {}))) is not None
    }
    out: Dict[str, float] = {"games": len(per_game_raw),
                             "games_normalized": len(norm)}
    if norm:
        out["median_script_normalized"] = _median(norm.values())
        out["mean_script_normalized"] = sum(norm.values()) / len(norm)
        # the median alone flatters a sweep where some games sit at the
        # floor (VERDICT r3): ship the per-game map and the floor count so
        # the headline can't be quoted without its caveat
        out["per_game_normalized"] = {g: round(n, 4)
                                      for g, n in sorted(norm.items())}
        out["games_below_0.2"] = sum(1 for n in norm.values() if n < 0.2)
        # scripted ceilings are asymmetric (VERDICT r4): where the agent
        # BEATS its script (n > 1) the script was floor-quality and "1.0 =
        # plays like the script" understates the agent; the count makes the
        # two meanings of the median separable at a glance
        out["games_above_script"] = sum(1 for n in norm.values() if n > 1.0)
    return out


def _csv_scalar(text: str):
    """Invert csv.DictWriter's stringification for prior-row reload: ints,
    floats and bools come back typed; everything else stays a string."""
    if text in ("True", "False"):
        return text == "True"
    for cast in (int, float):
        try:
            return cast(text)
        except ValueError:
            continue
    return text


def load_prior_rows(results_dir: str, skip_games: List[str]):
    """Reload completed per_game.csv rows for games NOT in this run, so a
    partial rerun (crash-resume, or topping up one game's budget) keeps the
    other games' committed rows instead of overwriting them.  Returns
    (rows, per_game_raw, baselines, failed) in run_sweep's working shapes;
    rows with an error marker reload as rows + a `failed` entry — never into
    the aggregate's score maps — so the rewritten aggregate keeps the
    games_failed caveat the first run's flush() wrote (writer-emits-caveats
    rule: dropping it on resume would un-declare a recorded failure)."""
    import csv as _csv

    path = os.path.join(results_dir, "per_game.csv")
    rows, per_game, baselines, failed = [], {}, {}, []
    if not os.path.exists(path):
        return rows, per_game, baselines, failed
    with open(path, newline="") as f:
        for raw in _csv.DictReader(f):
            game = raw.get("game")
            if not game or game in skip_games:
                continue
            row = {k: _csv_scalar(v) for k, v in raw.items() if v != ""}
            rows.append(row)
            if "error" in row or row.get("score_mean") is None:
                failed.append(game)
            else:
                per_game[game] = row["score_mean"]
                baselines[game] = {"random": row.get("random_baseline"),
                                   "scripted": row.get("scripted_baseline")}
    return rows, per_game, baselines, failed


def run_sweep(base_args: List[str], games: Optional[List[str]] = None,
              results_dir: str = "results/jaxsuite",
              baseline_episodes: int = 64,
              per_game_args: Optional[Dict[str, List[str]]] = None,
              note: Optional[str] = None,
              resume_rows: bool = False) -> Dict[str, object]:
    """Train+eval each jax game via the training CLI (mirror of
    atari57.run_sweep), then aggregate against measured baselines.

    ``per_game_args`` appends extra CLI flags for specific games (e.g. a
    bigger ``--t-max`` for the games whose scripted ceilings encode
    trajectory-level skill).  per_game.csv and aggregate.json are rewritten
    after EVERY game, so an interrupted sweep keeps its completed rows.
    ``note`` rides into aggregate.json verbatim (ADVICE r4: caveats must be
    emitted by the writer, not hand-patched into the artifact, or a rerun
    silently drops them); per-game frame budgets are emitted the same way.
    ``resume_rows`` seeds from the existing per_game.csv (games being rerun
    excluded), so restarting a killed sweep with only its unfinished games
    cannot overwrite the finished ones."""
    from rainbow_iqn_apex_tpu.atari57 import (
        SweepChildFailed,
        pin_sweep_parent_to_cpu,
        train_one_game,
        write_results_csv,
    )

    pin_sweep_parent_to_cpu()  # baselines + salvage below: never on the chip
    games = games or JAXSUITE
    per_game: Dict[str, float] = {}
    baselines: Dict[str, Dict] = {}
    rows = []
    failed = []
    if resume_rows:
        rows, per_game, baselines, failed = load_prior_rows(results_dir,
                                                            games)

    def flush():
        write_results_csv(os.path.join(results_dir, "per_game.csv"), rows)
        agg = aggregate(per_game, baselines)
        agg["games_failed"] = len(failed)
        if failed:
            agg["failed_games"] = failed
        # partial-budget (salvaged) scores sit in the same median — the
        # aggregate must say so itself (writer-emits-caveats rule)
        salvaged = sorted(r["game"] for r in rows if r.get("salvaged"))
        if salvaged:
            agg["games_salvaged"] = len(salvaged)
            agg["salvaged_games"] = salvaged
        frames = {r["game"]: r["train_frames"] for r in rows
                  if r.get("train_frames") is not None}
        if frames:
            agg["train_frames_per_game"] = frames  # always a dict: a
            # schema that flips to a scalar when budgets happen to agree
            # breaks consumers on the next per-game override
        if note:
            agg["note"] = note
        with open(os.path.join(results_dir, "aggregate.json"), "w") as f:
            json.dump(agg, f, indent=2)
        return agg

    for game in games:
        args = [*base_args, *(per_game_args or {}).get(game, [])]
        run_id = f"jaxsuite_{game}"
        try:
            summary = train_one_game(f"jaxgame:{game}", run_id, args)
        except SweepChildFailed as e:
            # a non-zero exit is a failed game, never a salvage: whatever
            # checkpoint sits under this run id is not this run's result
            failed.append(game)
            rows.append({"game": game, "score_mean": None, "error": str(e)})
            flush()
            continue
        raw = summary.get("eval_score_mean")
        extra = dict(summary)
        salvaged = False
        if raw is None:
            # a training that ended without a final score (wound down
            # early) still leaves periodic checkpoints — score the latest
            # one rather than dropping hours of training; ANY salvage
            # failure becomes an error row so one broken game can never
            # abort the remaining sweep
            try:
                raw, ck_extra = eval_checkpoint_fused(
                    args, run_id, game, episodes=baseline_episodes,
                    with_extra=True)
                salvaged = True
                extra = {"eval_episodes": baseline_episodes,
                         "frames": ck_extra.get("frames")}
            except FileNotFoundError:
                failed.append(game)
                rows.append({"game": game, "score_mean": None,
                             "error": "training run failed "
                                      "(no checkpoint to salvage)"})
                flush()
                continue
            except Exception as e:  # noqa: BLE001 — keep the sweep alive
                failed.append(game)
                rows.append({"game": game, "score_mean": None,
                             "error": f"salvage eval failed: {e!r}"})
                flush()
                continue
        baselines[game] = measure_baselines(game, episodes=baseline_episodes)
        per_game[game] = raw
        row = {
            "game": game,
            "score_mean": raw,
            "random_baseline": baselines[game].get("random"),
            "scripted_baseline": baselines[game].get("scripted"),
            "script_normalized": normalized_score(raw, baselines[game]),
            "train_frames": extra.get("frames"),
            **{k: v for k, v in extra.items() if k.startswith("eval_")},
        }
        if salvaged:
            row["salvaged"] = True  # scored from the latest periodic
            # checkpoint of an interrupted run, at its true frame count
        rows.append(row)
        flush()
    return flush()


# ------------------------------------------------- generalization (Procgen)


def eval_checkpoint_per_level(base_args: List[str], run_id: str,
                              base_game: str, levels,
                              episodes_per_level: int = 8, seed: int = 4321,
                              chunk_levels: int = 16,
                              max_ticks: Optional[int] = None) -> np.ndarray:
    """[n_levels, episodes_per_level] first-episode returns of a trained
    checkpoint with each lane PINNED to a known level (envs.device_games
    ``init_at_level``) — the measurement VERDICT r4 asked for: the two-pool
    eval can't separate a generalization gap from level-difficulty variance
    at 16-level pools, but per-level means over a 64+ level held-out set
    can.  Levels are free (`fold_in(base, level)`), so this is eval-cost
    only.

    The lane->level assignment rides through the rollout's `aux` argument,
    so every chunk of ``chunk_levels`` levels reuses ONE compiled rollout.
    Works for feedforward AND r2d2 checkpoints (greedy LSTM lanes with
    cut-reset, mirroring build_fused_r2d2_eval)."""
    from rainbow_iqn_apex_tpu.config import parse_config
    from rainbow_iqn_apex_tpu.envs.device_games import build_rollout
    from rainbow_iqn_apex_tpu.utils.checkpoint import Checkpointer

    cfg = parse_config([*base_args, "--env-id", f"jaxgame:{base_game}@var",
                        "--run-id", run_id])
    levels = list(levels)
    game = make_device_game(f"{base_game}@var")
    h, w = game.frame_shape
    T = max_ticks or tick_budget(base_game)
    eps = episodes_per_level
    C = min(chunk_levels, len(levels))
    lanes = C * eps

    def init_fn(aux, key):
        lane_levels = jnp.repeat(aux[1], eps)
        return jax.vmap(game.init_at_level)(
            lane_levels, jax.random.split(key, lanes)
        )

    if cfg.architecture == "r2d2":
        from rainbow_iqn_apex_tpu.ops.r2d2 import (
            build_r2d2_act_step,
            init_r2d2_state,
        )

        act_fn = build_r2d2_act_step(cfg, game.num_actions,
                                     use_noise=cfg.eval_noisy)

        def action_fn(aux, states, stack, key, lstm):
            a, _q, lstm = act_fn(aux[0], stack, lstm, key)
            return a, lstm

        from rainbow_iqn_apex_tpu.models.cores import make_core

        run = build_rollout(game, action_fn, lanes, T,
                            history=cfg.history_length,
                            actor_init=make_core(cfg).initial_state,
                            init_fn=init_fn)
        ts = init_r2d2_state(cfg, game.num_actions, jax.random.PRNGKey(0),
                             (h, w))
    else:
        from rainbow_iqn_apex_tpu.ops.learn import (
            build_act_step,
            init_train_state,
        )

        act_fn = build_act_step(cfg, game.num_actions, use_noise=False)

        def action_fn(aux, states, stack, key):
            actions, _q = act_fn(aux[0], stack, key)
            return actions

        run = build_rollout(game, action_fn, lanes, T,
                            history=cfg.history_length, init_fn=init_fn)
        ts = init_train_state(cfg, game.num_actions, jax.random.PRNGKey(0),
                              state_shape=(h, w, cfg.history_length))
    ckpt = Checkpointer(os.path.join(cfg.checkpoint_dir, cfg.run_id))
    if ckpt.latest_step() is None:
        raise FileNotFoundError(
            f"no checkpoint under {cfg.checkpoint_dir}/{cfg.run_id}"
        )
    ts, _ = ckpt.restore(ts)
    out = np.empty((len(levels), eps))
    for i in range(0, len(levels), C):
        chunk = levels[i:i + C]
        pad = C - len(chunk)  # final partial chunk: repeat the last level
        arr = jnp.asarray(chunk + [chunk[-1]] * pad, jnp.int32)
        scores = np.asarray(run((ts.params, arr), jax.random.PRNGKey(seed + i)))
        out[i:i + len(chunk)] = scores.reshape(C, eps)[:len(chunk)]
    return out


def bootstrap_gap(train_level_means, heldout_level_means,
                  n_boot: int = 2000, seed: int = 0) -> Dict[str, object]:
    """Generalization gap with LEVEL-resampled uncertainty.  The unit of
    variance that round-4's negative gaps exposed is the level, not the
    episode, so both pools are bootstrapped over level means;
    ``gap_boot_frac_positive`` near 0.5 says the gap's sign is noise,
    near 0 or 1 says it is stable under resampling the pools (VERDICT r4
    item 4's acceptance bar)."""
    rng = np.random.default_rng(seed)
    tm = np.asarray(train_level_means, float)
    hm = np.asarray(heldout_level_means, float)
    it = rng.integers(0, len(tm), (n_boot, len(tm)))
    ih = rng.integers(0, len(hm), (n_boot, len(hm)))
    gaps = tm[it].mean(axis=1) - hm[ih].mean(axis=1)
    return {
        "gap": float(tm.mean() - hm.mean()),
        "gap_boot_frac_positive": float((gaps > 0).mean()),
        "gap_boot_ci90": [float(np.quantile(gaps, 0.05)),
                          float(np.quantile(gaps, 0.95))],
    }


def per_level_fields(train_scores: np.ndarray, heldout_scores: np.ndarray,
                     first_heldout_level: int) -> Dict[str, object]:
    """The generalization row's per-level block: level means, across-level
    spread, and the bootstrap gap-sign stability."""
    tm, hm = train_scores.mean(axis=1), heldout_scores.mean(axis=1)
    return {
        "episodes_per_level": int(train_scores.shape[1]),
        "n_train_levels": int(len(tm)),
        "n_heldout_levels": int(len(hm)),
        "first_heldout_level": int(first_heldout_level),
        "train_level_means": [round(float(x), 4) for x in tm],
        "heldout_level_means": [round(float(x), 4) for x in hm],
        "train_mean": round(float(tm.mean()), 4),
        "train_std_across_levels": round(float(tm.std(ddof=1)), 4),
        "heldout_mean": round(float(hm.mean()), 4),
        "heldout_std_across_levels": round(float(hm.std(ddof=1)), 4),
        **bootstrap_gap(tm, hm),
    }


def eval_checkpoint_fused(base_args: List[str], run_id: str, game_name: str,
                          episodes: int = 64, seed: int = 1234,
                          with_extra: bool = False):
    """Mean first-episode return of a trained checkpoint on `game_name`
    (variant ids welcome), via the in-graph fused eval — the measurement
    half of the train/test generalization split.  ``with_extra=True``
    returns ``(score, extra)`` where extra is the checkpoint's JSON side-car
    (frames counter etc.) — the salvage paths need it and the restore has it
    in hand anyway."""
    from rainbow_iqn_apex_tpu.config import parse_config
    from rainbow_iqn_apex_tpu.utils.checkpoint import Checkpointer

    cfg = parse_config(
        [*base_args, "--env-id", f"jaxgame:{game_name}", "--run-id", run_id]
    )
    game = make_device_game(game_name)
    h, w = game.frame_shape
    T = tick_budget(game_name)
    if cfg.architecture == "r2d2":
        from rainbow_iqn_apex_tpu.ops.r2d2 import init_r2d2_state
        from rainbow_iqn_apex_tpu.train_anakin_r2d2 import build_fused_r2d2_eval

        ts = init_r2d2_state(cfg, game.num_actions, jax.random.PRNGKey(0),
                             (h, w))
        eval_fn = build_fused_r2d2_eval(cfg, game, episodes, max_ticks=T)
    else:
        from rainbow_iqn_apex_tpu.ops.learn import init_train_state
        from rainbow_iqn_apex_tpu.train_anakin import build_fused_eval

        ts = init_train_state(cfg, game.num_actions, jax.random.PRNGKey(0),
                              state_shape=(h, w, cfg.history_length))
        eval_fn = build_fused_eval(cfg, game, episodes, max_ticks=T)
    ckpt = Checkpointer(os.path.join(cfg.checkpoint_dir, cfg.run_id))
    if ckpt.latest_step() is None:
        raise FileNotFoundError(
            f"no checkpoint under {cfg.checkpoint_dir}/{cfg.run_id}"
        )
    ts, ck_extra = ckpt.restore(ts)
    scores = np.asarray(eval_fn(ts.params, jax.random.PRNGKey(seed)))
    score = float(scores.mean())
    return (score, ck_extra) if with_extra else score


def run_generalization(base_args: List[str],
                       games: Optional[List[str]] = None,
                       results_dir: str = "results/jaxsuite",
                       episodes: int = 64,
                       per_game_args: Optional[Dict[str, List[str]]] = None,
                       note: Optional[str] = None,
                       levels_eval: int = 64,
                       episodes_per_level: int = 8) -> Dict:
    """Procgen-class generalization check (BASELINE.md config 5 stand-in):
    train each variant game on its 16-seed TRAIN level pool
    (jaxgame:<g>@var), then eval the SAME checkpoint on train levels and on
    the 16 held-out levels (@var-test).  Writes
    results_dir/generalization.json with per-game train/test scores, the
    generalization gap, and the TRAIN-pool random baseline (a train score
    that does not clearly beat random makes the gap meaningless — VERDICT
    r3: such rows are reported with ``off_random: false`` so consumers can
    filter them).  The JSON is rewritten after every game, and
    ``per_game_args`` appends per-game flags (e.g. bigger ``--t-max`` for
    slower-learning games).

    ``levels_eval > 0`` adds a ``per_level`` block per row: the checkpoint
    is additionally evaluated with lanes pinned to each of the 16 train
    levels and to ``levels_eval`` held-out levels (ids 16..16+levels_eval-1
    — the first 16 are the @var-test pool, the rest are drawn from the same
    generative process and are equally unseen), reporting per-level means,
    across-level spread, and a level-bootstrap of the gap's sign (VERDICT
    r4: a ±2-point two-pool gap at 16-level pools is indistinguishable from
    pool-difficulty variance)."""
    from rainbow_iqn_apex_tpu.atari57 import (
        SweepChildFailed,
        pin_sweep_parent_to_cpu,
        train_one_game,
    )
    from rainbow_iqn_apex_tpu.envs.device_games import VARIANT_GAMES

    pin_sweep_parent_to_cpu()  # checkpoint evals below: never on the chip
    games = list(games or sorted(VARIANT_GAMES))
    unsupported = [g for g in games if g not in VARIANT_GAMES]
    if unsupported:
        raise ValueError(
            f"no seeded-variant mode for {unsupported} (have: "
            f"{sorted(VARIANT_GAMES)})"
        )
    rows = []
    os.makedirs(results_dir, exist_ok=True)

    def flush():
        out = {"episodes_per_split": episodes, "per_game": rows}
        if note:
            out["note"] = note
        with open(os.path.join(results_dir, "generalization.json"), "w") as f:
            json.dump(out, f, indent=2)
        return out

    for g in games:
        run_id = f"jaxsuite_{g}_var"
        args = [*base_args, *(per_game_args or {}).get(g, [])]
        try:
            summary = train_one_game(f"jaxgame:{g}@var", run_id, args)
        except SweepChildFailed as e:
            rows.append({"game": g, "error": str(e)})
            flush()
            continue
        trained_ok = summary.get("eval_score_mean") is not None
        try:
            # both splits are scored from the checkpoint anyway, so an
            # interrupted/killed training salvages for free — the row just
            # carries `salvaged` and the checkpoint's true frame count
            train_score, ck_extra = eval_checkpoint_fused(
                args, run_id, f"{g}@var", episodes, with_extra=True)
            test_score = eval_checkpoint_fused(args, run_id, f"{g}@var-test",
                                               episodes)
        except FileNotFoundError:
            # distinguish the mislabel: a COMPLETED training with no
            # checkpoint is a misconfiguration, not a failed run
            rows.append({"game": g, "error":
                         "trained but no checkpoint found (checkpointing "
                         "misconfigured?)" if trained_ok else
                         "training run failed (no checkpoint to salvage)"})
            flush()
            continue
        except Exception as e:  # noqa: BLE001 — keep remaining games alive
            rows.append({"game": g, "error": f"checkpoint eval failed: {e!r}"})
            flush()
            continue
        train_frames = (summary.get("frames") if trained_ok
                        else ck_extra.get("frames"))
        rnd = float(np.mean(rollout_returns(f"{g}@var", _p_random, episodes,
                                            seed=99)))
        # the "clearly off-random" bar: random plus 2x its magnitude (i.e.
        # 3x random when random > 0), or +0.5 absolute when random is ~0 —
        # for negative random baselines (catch-style symmetric scores) this
        # is |random|, comfortably above zero (ADVICE r4 wording fix)
        bar = rnd + max(2.0 * abs(rnd), 0.5)
        row = {
            "game": g,
            "train_levels_score": train_score,
            "heldout_levels_score": test_score,
            "generalization_gap": train_score - test_score,
            "train_random_baseline": rnd,
            "off_random": bool(train_score >= bar),
            "train_frames": train_frames,
        }
        if not trained_ok:
            row["salvaged"] = True  # scored from the latest periodic
            # checkpoint of an interrupted run
        # the two-pool row is hours of training — it goes to disk BEFORE the
        # per-level eval can fail (compile OOM, corrupted checkpoint); the
        # block is added by a re-flush
        rows.append(row)
        flush()
        if levels_eval > 0:
            from rainbow_iqn_apex_tpu.envs.device_games import N_TRAIN_LEVELS

            try:
                # one call over both pools = one compile + one restore (the
                # compile dominates eval cost on CPU); split afterwards
                all_pl = eval_checkpoint_per_level(
                    args, run_id, g,
                    range(N_TRAIN_LEVELS + levels_eval), episodes_per_level)
                row["per_level"] = per_level_fields(
                    all_pl[:N_TRAIN_LEVELS], all_pl[N_TRAIN_LEVELS:],
                    N_TRAIN_LEVELS)
            except Exception as e:  # noqa: BLE001 — never lose the row
                row["per_level_error"] = repr(e)
            flush()
    return flush()
