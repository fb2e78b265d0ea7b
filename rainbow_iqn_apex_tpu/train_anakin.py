"""Anakin trainer: the whole Rainbow-IQN learner ON the chip — device-resident
PER replay (replay/device.py) + the fused sample->learn->write-back tick —
with host envs feeding one small [L, H, W] frame tensor per tick.

Reference parity: same algorithm and schedules as the single-process mode
(`train.py`, SURVEY.md §3.1+§3.2) — act/learn interleaved at `frames_per_learn`,
n-step PER with the reference's max-priority insertion for fresh transitions,
scheduled target update (inside the learn graph), Orbax checkpoints, JSONL
metrics, periodic eval.  What changes is WHERE the replay lives: the
reference keeps it in Redis (a network hop per sample, SURVEY §2 row 6), the
host trainers here keep it in host DRAM (a PCIe hop), and this one keeps it
in HBM — zero per-step transfer (what share of the host-fed step that
transfer is on a directly attached chip is not measured; ROADMAP S2).

Per tick, exactly TWO dispatches and ~7 KB/lane of host->device traffic:
  1. act_append: append LAST tick's completed transition into the HBM ring
     (lag-one, so reward/terminal are known) + shift the device-resident
     frame stack + act on it.
  2. fused learn (when due): sample + learn + priority write-back, one graph.
"""

from __future__ import annotations

import collections
import functools
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from rainbow_iqn_apex_tpu.agents.agent import put_frames
from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.envs import make_vector_env
from rainbow_iqn_apex_tpu.obs import RunObs, device_scopes
from rainbow_iqn_apex_tpu.ops.learn import build_act_step, init_train_state
from rainbow_iqn_apex_tpu.parallel.multihost import shift_stack
from rainbow_iqn_apex_tpu.replay.device import DeviceReplay, build_device_learn
from rainbow_iqn_apex_tpu.train import priority_beta
from rainbow_iqn_apex_tpu.utils.checkpoint import Checkpointer, maybe_resume
from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger


def _replay_snapshot_path(cfg: Config) -> str:
    return os.path.join(cfg.checkpoint_dir, cfg.run_id, "replay_anakin.npz")


def _save_replay(cfg: Config, ds) -> None:
    if not cfg.snapshot_replay:
        return
    from rainbow_iqn_apex_tpu.replay import snapshot_io

    host = jax.device_get(ds)
    snapshot_io.atomic_savez(
        _replay_snapshot_path(cfg),
        frames=host.frames, actions=host.actions, rewards=host.rewards,
        terminals=host.terminals, cuts=host.cuts, priority=host.priority,
        pos=host.pos, filled=host.filled, max_priority=host.max_priority,
    )


def _maybe_restore_replay(cfg: Config, ds):
    """Returns (state, restored_ticks) — ticks drive the host-side warmness
    counters, which must match the restored ring."""
    path = _replay_snapshot_path(cfg)
    if not (cfg.snapshot_replay and os.path.exists(path)):
        return ds, 0
    from rainbow_iqn_apex_tpu.replay import snapshot_io

    z = snapshot_io.load(path)
    if tuple(z["frames"].shape) != tuple(ds.frames.shape):
        return ds, 0  # shape change: degrade to cold replay, same as host path
    ds = ds.replace(
        frames=jnp.asarray(z["frames"]), actions=jnp.asarray(z["actions"]),
        rewards=jnp.asarray(z["rewards"]), terminals=jnp.asarray(z["terminals"]),
        cuts=jnp.asarray(z["cuts"]), priority=jnp.asarray(z["priority"]),
        pos=jnp.asarray(z["pos"]), filled=jnp.asarray(z["filled"]),
        max_priority=jnp.asarray(z["max_priority"]),
    )
    return ds, int(z["filled"])


def train_anakin(cfg: Config, max_frames: Optional[int] = None) -> Dict[str, Any]:
    """Runs training; returns a summary dict (final eval, fps, steps).

    With a pure-JAX env (`jaxgame:*`) and `fused_env` on, dispatches to the
    fully fused variant (env compiled into the graph) below."""
    if cfg.replay_ratio > 1:
        raise ValueError(
            "replay_ratio > 1 (clipped replay reuse) targets the actor-bound "
            "apex/single loops; the anakin learner is already fused "
            "device-resident — reuse there is the recorded ROADMAP follow-up")
    if cfg.fused_env and cfg.env_id.startswith("jaxgame:"):
        return train_anakin_fused(cfg, max_frames)
    total_frames = max_frames or cfg.t_max
    lanes = cfg.num_envs_per_actor
    env = make_vector_env(cfg.env_id, lanes, seed=cfg.seed)
    if cfg.memory_capacity % lanes:
        raise ValueError(
            f"memory capacity {cfg.memory_capacity} not divisible by {lanes} lanes"
        )
    seg = cfg.memory_capacity // lanes
    replay = DeviceReplay(
        lanes=lanes, seg=seg, frame_shape=env.frame_shape,
        history=cfg.history_length, n_step=cfg.multi_step, gamma=cfg.gamma,
        priority_exponent=cfg.priority_exponent, priority_eps=cfg.priority_eps,
    )
    ds = replay.init_state()
    key = jax.random.PRNGKey(cfg.seed)
    key, k_init = jax.random.split(key)
    ts = init_train_state(
        cfg, env.num_actions, k_init,
        state_shape=(*env.frame_shape, cfg.history_length),
    )
    act_fn = build_act_step(cfg, env.num_actions, use_noise=True)

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def act_append(params, stack, ds, frame, keep, prev, key):
        """Dispatch 1: append last tick's completed transition (None on the
        first tick), shift the device stack, act."""
        if prev is not None:
            ds = replay.append(ds, *prev)
        stack = shift_stack(stack, frame, keep)
        a, _q = act_fn(params, stack, key)
        return a, stack, ds

    fused = jax.jit(
        build_device_learn(cfg, env.num_actions, replay), donate_argnums=(0, 1)
    )

    run_dir = os.path.join(cfg.results_dir, cfg.run_id)
    metrics = MetricsLogger(os.path.join(run_dir, "metrics.jsonl"), cfg.run_id)
    ckpt = Checkpointer(os.path.join(cfg.checkpoint_dir, cfg.run_id))
    obs_run = RunObs(cfg, metrics, role="learner")

    frames = 0
    ticks = 0
    restored = maybe_resume(cfg, ckpt, ts)
    if restored is not None:
        ts, extra, _ = restored
        frames = int(extra.get("frames", 0))
        ds, ticks = _maybe_restore_replay(cfg, ds)
        metrics.log("resume", step=int(ts.step), frames=frames)
    learn_steps = int(ts.step)

    h, w = env.frame_shape
    stack = jnp.zeros((lanes, h, w, cfg.history_length), jnp.uint8)
    obs = env.reset()
    prev_cuts = np.zeros(lanes, bool)
    prev = None  # device-resident (frame, action, reward, term, trunc) tuple
    returns: collections.deque = collections.deque(maxlen=100)
    device = jax.devices()[0]

    # --trace-dir: the capture's 'device_time' row resolves both programs'
    # ops to scopes, each in its own text (read when the capture closes, in
    # steady state: `prev` is a tuple then, the program the ticks run)
    obs_run.trace_window.add_program(lambda: act_append.lower(
        ts.params, stack, ds, frame_d, keep_d, prev, k).compile().as_text())
    obs_run.trace_window.add_program(lambda: fused.lower(
        ts, ds, k, jnp.float32(priority_beta(cfg, frames))
    ).compile().as_text())
    try:
        while frames < total_frames:
            frame_d = put_frames(obs)  # flat-byte staging (rank-3 put penalty)
            keep_d = jax.device_put((~prev_cuts).astype(np.uint8), device)
            key, k = jax.random.split(key)
            with obs_run.span("act_append"):
                actions_d, stack, ds = act_append(
                    ts.params, stack, ds, frame_d, keep_d, prev, k
                )
                actions = np.asarray(actions_d)
            new_obs, rewards, terminals, truncs, ep_returns = env.step(actions)
            # held for NEXT tick's append: reference memory layout (pre-step
            # frame + this step's action/reward/terminal, SURVEY §2 row 5); the
            # fresh-transition priority is the running max, exactly the
            # reference's single-process insertion rule.
            prev = (
                frame_d,
                actions_d,
                jax.device_put(rewards.astype(np.float32), device),
                jax.device_put(terminals, device),
                jax.device_put(truncs, device),
            )
            prev_cuts = terminals | truncs
            obs = new_obs
            frames += lanes
            ticks += 1
            for r in ep_returns[~np.isnan(ep_returns)]:
                returns.append(float(r))

            # warmness from host-side lockstep counters (appends lag one tick)
            stored = min(max(ticks - 1, 0), seg) * lanes
            if stored >= cfg.learn_start and ticks - 1 > cfg.multi_step:
                steps_due = frames // cfg.frames_per_learn - learn_steps
                for _ in range(max(steps_due, 0)):
                    key, k = jax.random.split(key)
                    with obs_run.span("learn_step"):
                        ts, ds, info = fused(
                            ts, ds, k, jnp.float32(priority_beta(cfg, frames))
                        )
                    learn_steps += 1
                    # no block_on: this loop's dispatches stay async between
                    # metrics intervals, and a per-step barrier would kill the
                    # host/device overlap that IS the anakin design.  StepTimer
                    # laps then measure dispatch gaps — steady-state the device
                    # queue throttles the host, so steps_per_sec stays true.
                    obs_run.after_learn_step(learn_steps)
                    if learn_steps % cfg.metrics_interval == 0:
                        metrics.log(
                            "learn",
                            step=learn_steps,
                            frames=frames,
                            fps=metrics.fps(frames),
                            loss=float(info["loss"]),
                            q_mean=float(info["q_mean"]),
                            grad_norm=float(info["grad_norm"]),
                            mean_return=float(np.mean(returns)) if returns else float("nan"),
                        )
                        obs_run.periodic(
                            learn_steps, frames,
                            replay_occupancy=round(stored / cfg.memory_capacity, 4),
                        )
                    if cfg.eval_interval and learn_steps % cfg.eval_interval == 0:
                        metrics.log("eval", step=learn_steps, **_eval(cfg, env, ts))
                    if cfg.checkpoint_interval and learn_steps % cfg.checkpoint_interval == 0:
                        ckpt.save(learn_steps, ts, {"frames": frames})
                        _save_replay(cfg, ds)

    finally:
        obs_run.close(learn_steps, frames)
    final_eval = _eval(cfg, env, ts)
    metrics.log("eval", step=learn_steps, **final_eval)
    ckpt.save(learn_steps, ts, {"frames": frames})
    _save_replay(cfg, ds)
    ckpt.wait()
    metrics.close()
    return {
        "frames": frames,
        "learn_steps": learn_steps,
        "train_return_mean": float(np.mean(returns)) if returns else float("nan"),
        **{f"eval_{k}": v for k, v in final_eval.items()},
    }


def _eval(cfg: Config, env, ts) -> Dict[str, Any]:
    from rainbow_iqn_apex_tpu.eval import evaluate_state

    return evaluate_state(cfg, env, ts, seed=cfg.seed + 977)


# ---------------------------------------------------------------------------
# Fully fused Anakin: the ENV inside the graph (jaxgame:* pure-JAX games)
# ---------------------------------------------------------------------------


def build_fused_segment(cfg: Config, game, replay: DeviceReplay, learn_fn):
    """The fused Anakin program: a jitted (carry, key) -> (carry, outs)
    scanning `cfg.anakin_segment_ticks` ticks of
    act -> env.step -> replay.append -> lax.cond(warm, k x learn).

    carry = (ts, ds, env_states, ep_returns, stack, frame, keep, frames);
    outs = per-tick (ep_return [L] NaN-except-on-cut, loss/q_mean/grad_norm
    [learns_per_tick] NaN-when-cold).  `learn_fn` is either the single-chip
    `build_device_learn` graph or the mesh-sharded
    `build_device_learn_sharded` one — the tick body is identical, which is
    what lets the trainer, the multichip dryrun, and the TPU capture harness
    share this exact program."""
    from rainbow_iqn_apex_tpu.envs.device_games import batched_reset_step

    lanes = cfg.num_envs_per_actor
    learns_per_tick = lanes // cfg.frames_per_learn
    seg = replay.seg
    act_fn = build_act_step(cfg, game.num_actions, use_noise=True)
    env_step = batched_reset_step(game)
    bw = cfg.priority_weight

    def tick(carry, k):
        ts, ds, env_s, ep, stack, frame, keep, frames = carry
        ka, ks, kl = jax.random.split(k, 3)
        with jax.named_scope(device_scopes.TICK_ACT):
            stack = shift_stack(stack, frame, keep)
            actions, _q = act_fn(ts.params, stack, ka)
        with jax.named_scope(device_scopes.TICK_ENV):
            env_s, ep, nframe, reward, term, trunc, out_ret = env_step(
                env_s, ep, actions, ks
            )
        # the completed transition, appended the same tick (the host loop's
        # lag-one bookkeeping exists only because its env stepped off-device)
        with jax.named_scope(device_scopes.TICK_APPEND):
            ds = replay.append(ds, frame, actions, reward, term, trunc)
        frames = frames + lanes

        stored = jnp.minimum(ds.filled, seg) * lanes
        warm = (stored >= cfg.learn_start) & (ds.filled > cfg.multi_step)
        beta = jnp.float32(
            bw + (1.0 - bw) * jnp.minimum(frames / float(cfg.t_max), 1.0)
        )

        def do_learn(args):
            ts, ds = args

            def one(c, kk):
                ts, ds = c
                ts, ds, info = learn_fn(ts, ds, kk, beta)
                return (ts, ds), (info["loss"], info["q_mean"], info["grad_norm"])

            (ts, ds), infos = jax.lax.scan(
                one, (ts, ds), jax.random.split(kl, learns_per_tick)
            )
            return ts, ds, infos

        def no_learn(args):
            ts, ds = args
            nanv = jnp.full((learns_per_tick,), jnp.nan, jnp.float32)
            return ts, ds, (nanv, nanv, nanv)

        with jax.named_scope(device_scopes.TICK_LEARN):
            ts, ds, infos = jax.lax.cond(warm, do_learn, no_learn, (ts, ds))
        keep = (~(term | trunc)).astype(jnp.uint8)
        out = (out_ret, infos[0], infos[1], infos[2])
        return (ts, ds, env_s, ep, stack, nframe, keep, frames), out

    @functools.partial(jax.jit, donate_argnums=(0,))
    def segment(carry, key):
        return jax.lax.scan(tick, carry, jax.random.split(key, cfg.anakin_segment_ticks))

    return segment


def build_fused_eval(cfg: Config, game, episodes: int, max_ticks: int = 1024):
    """In-graph evaluation: `episodes` parallel lanes played greedily (noise
    OFF, per-tick tau draws as in eval.py) for up to `max_ticks` — one
    jitted (params, key) -> returns call instead of per-step host dispatches
    through the Env adapter.  Built on the shared rollout core
    (envs/device_games.build_rollout): each lane scores its FIRST episode,
    with capped-return semantics at the tick budget."""
    from rainbow_iqn_apex_tpu.envs.device_games import build_rollout

    act_fn = build_act_step(cfg, game.num_actions, use_noise=False)

    def action_fn(params, states, stack, key):
        actions, _q = act_fn(params, stack, key)
        return actions

    return build_rollout(game, action_fn, episodes, max_ticks,
                         history=cfg.history_length)


def fused_eval_scores(eval_fn, params, key) -> Dict[str, Any]:
    """Host-side summary of build_fused_eval's output, with the same keys as
    eval.evaluate (so metrics rows are interchangeable)."""
    scores = np.asarray(eval_fn(params, key))
    return {
        "episodes": int(len(scores)),
        "score_mean": float(scores.mean()),
        "score_median": float(np.median(scores)),
        "score_min": float(scores.min()),
        "score_max": float(scores.max()),
    }


def init_fused_carry(cfg: Config, game, replay: DeviceReplay, ts, ds, key,
                     frames: int = 0):
    """Fresh lane states + empty device stack for build_fused_segment."""
    from rainbow_iqn_apex_tpu.envs.device_games import batched_init

    lanes = cfg.num_envs_per_actor
    h, w = game.frame_shape
    env_s = batched_init(game, key, lanes)
    ep = jnp.zeros(lanes)
    stack = jnp.zeros((lanes, h, w, cfg.history_length), jnp.uint8)
    frame = jax.vmap(game.render)(env_s)
    keep = jnp.ones(lanes, jnp.uint8)
    return (ts, ds, env_s, ep, stack, frame, keep, jnp.int32(frames))


def train_anakin_fused(cfg: Config, max_frames: Optional[int] = None) -> Dict[str, Any]:
    """Everything on chip: act -> env.step -> replay.append -> (learn x k),
    scanned over `anakin_segment_ticks` ticks per dispatch.

    This is the Podracer/Anakin topology proper — the reference's whole
    actor+learner+Redis loop (SURVEY §3.1-3.2) collapses into ONE jitted
    program; host traffic is a handful of scalars per segment for metrics.
    Semantics kept from the host anakin path: same IQN learn graph, same
    max-priority fresh insertion, same two-channel terminal/truncation cuts,
    same beta anneal (computed in-graph from the frame counter), learning
    gated in-graph on the same warmness rule.  One deliberate deviation: the
    learn cadence is `lanes/frames_per_learn` steps per tick (lanes must divide
    by frames_per_learn), the in-graph form of `frames // frames_per_learn`.
    """
    from rainbow_iqn_apex_tpu.envs.device_games import make_device_game

    total_frames = max_frames or cfg.t_max
    lanes = cfg.num_envs_per_actor
    if lanes % cfg.frames_per_learn:
        raise ValueError(
            f"fused anakin needs lanes ({lanes}) divisible by frames_per_learn "
            f"({cfg.frames_per_learn}) — the learn cadence is in-graph"
        )
    T = cfg.anakin_segment_ticks
    game = make_device_game(
        cfg.env_id.split(":", 1)[1], cfg.device_game_tick_cap)
    h, w = game.frame_shape
    if cfg.memory_capacity % lanes:
        raise ValueError(
            f"memory capacity {cfg.memory_capacity} not divisible by {lanes} lanes"
        )
    seg = cfg.memory_capacity // lanes
    replay = DeviceReplay(
        lanes=lanes, seg=seg, frame_shape=(h, w),
        history=cfg.history_length, n_step=cfg.multi_step, gamma=cfg.gamma,
        priority_exponent=cfg.priority_exponent, priority_eps=cfg.priority_eps,
    )
    key = jax.random.PRNGKey(cfg.seed)
    key, k_init, k_env = jax.random.split(key, 3)
    ts = init_train_state(
        cfg, game.num_actions, k_init, state_shape=(h, w, cfg.history_length)
    )

    # multi-device: one dp mesh; env lanes + HBM replay lane-sharded over it,
    # learn dp-sharded with per-shard draws (build_device_learn_sharded) —
    # the env/act/append half needs no collectives, so GSPMD shards it from
    # the lane-dim placements alone.  learner_devices follows the config
    # contract: 0 = all visible devices (anakin has no separate actor mesh).
    n_dev = cfg.learner_devices if cfg.learner_devices > 0 else len(jax.devices())
    mesh = None
    if n_dev > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from rainbow_iqn_apex_tpu.replay.device import (
            build_device_learn_sharded,
            device_replay_shardings,
        )

        if lanes % n_dev or cfg.batch_size % n_dev:
            raise ValueError(
                f"fused anakin over {n_dev} devices needs lanes ({lanes}) and "
                f"batch ({cfg.batch_size}) divisible by the device count"
            )
        mesh = Mesh(np.array(jax.devices()[:n_dev]), ("dp",))
        local_replay = DeviceReplay(
            lanes=lanes // n_dev, seg=seg, frame_shape=(h, w),
            history=cfg.history_length, n_step=cfg.multi_step, gamma=cfg.gamma,
            priority_exponent=cfg.priority_exponent, priority_eps=cfg.priority_eps,
        )
        learn_fn = build_device_learn_sharded(cfg, game.num_actions,
                                              local_replay, mesh)
        _lane = NamedSharding(mesh, P("dp"))
        _rep = NamedSharding(mesh, P())
        # the ring is born sharded: built whole it would sit on the first
        # chip (twice over while place() copies it out), and a ring sized for
        # the mesh's memory does not fit one chip's
        init_replay = jax.jit(
            replay.init_state, out_shardings=device_replay_shardings(mesh))

        def place(carry):
            ts, ds, env_s, ep, stack, frame, keep, frames = carry
            lane_tree = jax.tree.map(lambda x: jax.device_put(x, _lane),
                                     (env_s, ep, stack, frame, keep))
            return (
                jax.device_put(ts, _rep),
                jax.device_put(ds, device_replay_shardings(mesh)),
                *lane_tree,
                jax.device_put(frames, _rep),
            )
    else:
        learn_fn = build_device_learn(cfg, game.num_actions, replay)
        init_replay = replay.init_state
        place = lambda carry: carry  # noqa: E731

    segment = build_fused_segment(cfg, game, replay, learn_fn)

    run_dir = os.path.join(cfg.results_dir, cfg.run_id)
    metrics = MetricsLogger(os.path.join(run_dir, "metrics.jsonl"), cfg.run_id)
    ckpt = Checkpointer(os.path.join(cfg.checkpoint_dir, cfg.run_id))
    obs_run = RunObs(cfg, metrics, role="learner")

    frames = 0
    ds = init_replay()
    restored = maybe_resume(cfg, ckpt, ts)
    if restored is not None:
        ts, extra, _ = restored
        frames = int(extra.get("frames", 0))
        # replay snapshot only on an actual resume (host-path parity): a
        # fresh run with the same run_id must cold-start its ring
        ds, _ = _maybe_restore_replay(cfg, ds)
        metrics.log("resume", step=int(ts.step), frames=frames)
    learn_steps = int(ts.step)

    carry = place(init_fused_carry(cfg, game, replay, ts, ds, k_env, frames))

    # eval is in-graph too: greedy lanes scanned on device, one dispatch
    from rainbow_iqn_apex_tpu.envs.device_games import tick_budget

    game_name = cfg.env_id.split(":", 1)[1]
    eval_fn = build_fused_eval(
        cfg, game, cfg.eval_episodes, max_ticks=tick_budget(game_name, 1024)
    )

    def run_eval(params, step_no: int) -> Dict[str, Any]:
        # deterministic per eval point (bit-reproducible curves, as eval.py)
        k = jax.random.fold_in(jax.random.PRNGKey(cfg.seed + 977), step_no)
        return fused_eval_scores(eval_fn, params, k)

    returns: collections.deque = collections.deque(maxlen=100)

    def crossed(interval: int, before: int, after: int) -> bool:
        return interval > 0 and before // interval != after // interval

    # --trace-dir: the capture's 'device_time' row names the segment's work
    # by scope from the compiled text (no compile: the program has run)
    obs_run.trace_window.add_program(
        lambda: segment.lower(carry, k).compile().as_text())
    try:
        while frames < total_frames:
            key, k = jax.random.split(key)
            with obs_run.span("segment", ticks=T):
                carry, (out_ret, loss, q_mean, grad_norm) = segment(carry, k)
                ts, ds = carry[0], carry[1]
                frames += T * lanes
                prev_steps = learn_steps
                learn_steps = int(ts.step)  # in-graph counter is authoritative
            # the segment IS the dispatch unit here; the int(ts.step) readback
            # above already synced, so the lap needs no extra block.  units:
            # the timing row's steps_per_sec counts learn steps, not segments
            obs_run.after_learn_step(learn_steps, units=learn_steps - prev_steps)
            for r in np.asarray(out_ret)[~np.isnan(np.asarray(out_ret))]:
                returns.append(float(r))

            if crossed(cfg.metrics_interval, prev_steps, learn_steps):
                l = np.asarray(loss)
                metrics.log(
                    "learn",
                    step=learn_steps,
                    frames=frames,
                    fps=metrics.fps(frames),
                    loss=float(np.nanmean(l)) if np.any(~np.isnan(l)) else float("nan"),
                    q_mean=float(np.nanmean(np.asarray(q_mean)))
                    if np.any(~np.isnan(np.asarray(q_mean))) else float("nan"),
                    grad_norm=float(np.nanmean(np.asarray(grad_norm)))
                    if np.any(~np.isnan(np.asarray(grad_norm))) else float("nan"),
                    mean_return=float(np.mean(returns)) if returns else float("nan"),
                )
                obs_run.periodic(learn_steps, frames)
            if crossed(cfg.eval_interval, prev_steps, learn_steps):
                metrics.log("eval", step=learn_steps,
                            **run_eval(carry[0].params, learn_steps))
            if crossed(cfg.checkpoint_interval, prev_steps, learn_steps):
                ckpt.save(learn_steps, ts, {"frames": frames})
                _save_replay(cfg, ds)

    finally:
        obs_run.close(learn_steps, frames)
    final_eval = run_eval(carry[0].params, learn_steps)
    metrics.log("eval", step=learn_steps, **final_eval)
    ckpt.save(learn_steps, ts, {"frames": frames})
    _save_replay(cfg, ds)
    ckpt.wait()
    metrics.close()
    return {
        "frames": frames,
        "learn_steps": learn_steps,
        "train_return_mean": float(np.mean(returns)) if returns else float("nan"),
        **{f"eval_{k}": v for k, v in final_eval.items()},
    }
