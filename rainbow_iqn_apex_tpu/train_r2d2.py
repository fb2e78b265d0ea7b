"""R2D2 training loop: recurrent actor + stored-state sequence replay.

Parity: the reference's R2D2 stretch configuration (BASELINE.json:10,
SURVEY.md §7 step 7).  Mirrors train.py's act/learn interleave, with the
frame-stack replaced by the LSTM state the actor threads through time and
the transition replay replaced by SequenceReplay.
"""

from __future__ import annotations

import collections
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from rainbow_iqn_apex_tpu.agents.agent import FrameStacker
from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.envs import make_env, make_vector_env
from rainbow_iqn_apex_tpu.models.cores import make_core
from rainbow_iqn_apex_tpu.obs import RunObs
from rainbow_iqn_apex_tpu.ops.r2d2 import (
    as_actor_input,
    build_r2d2_act_step,
    build_r2d2_learn_step,
    init_r2d2_state,
    stem_from_frames_share,
    to_device_seq_batch,
)
from rainbow_iqn_apex_tpu.replay.sequence import SequenceReplay
from rainbow_iqn_apex_tpu.train import priority_beta
from rainbow_iqn_apex_tpu.utils.checkpoint import (
    Checkpointer,
    maybe_restore_replay,
    maybe_resume,
    rng_from_extra,
    save_replay_snapshot,
)
from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger


class R2D2Agent:
    """Host facade: recurrent act/learn with explicit LSTM state."""

    def __init__(self, cfg: Config, num_actions: int, frame_shape, key, train=True):
        self.cfg = cfg
        self.num_actions = num_actions
        self.core = make_core(cfg)
        key, k_init = jax.random.split(key)
        self.key = key
        self.state = init_r2d2_state(cfg, num_actions, k_init, frame_shape)
        self._act = jax.jit(build_r2d2_act_step(cfg, num_actions))
        self._act_eval = jax.jit(
            build_r2d2_act_step(cfg, num_actions, use_noise=cfg.eval_noisy)
        )
        self._learn = (
            jax.jit(build_r2d2_learn_step(cfg, num_actions), donate_argnums=0)
            if train
            else None
        )

    def _next_key(self):
        self.key, k = jax.random.split(self.key)
        return k

    def initial_lstm_state(self, batch: int):
        return self.core.initial_state(batch)

    def act(self, obs, lstm_state, eval_mode=False):
        """obs [B, H, W] u8 (history 1) or [B, H, W, hist] stacked ->
        (actions [B], new_state)."""
        fn = self._act_eval if eval_mode else self._act
        x = as_actor_input(obs, self.cfg.history_length)
        a, q, new_state = fn(self.state.params, x, lstm_state, self._next_key())
        return np.asarray(a), new_state

    def learn(self, sample) -> Dict[str, Any]:
        self.state, info = self._learn(
            self.state, to_device_seq_batch(sample), self._next_key()
        )
        return info

    @property
    def step(self) -> int:
        return int(self.state.step)


def _mask_reset(core, lstm_state, terminals: np.ndarray):
    """The core's state with the lanes whose episode just ended back at the
    start."""
    return core.reset_lanes(
        lstm_state, jnp.asarray(1.0 - np.asarray(terminals, np.float32)))


def evaluate_r2d2(cfg: Config, agent: R2D2Agent, episodes: Optional[int] = None,
                  seed: int = 0, max_steps: int = 200_000,
                  env=None) -> Dict[str, Any]:
    """``env`` overrides the cfg.env_id default — the multi-game apex path
    hands in each game's padded GameLaneEnv (docs/MULTITASK.md)."""
    episodes = episodes or cfg.eval_episodes
    env = env if env is not None else make_env(cfg.env_id, seed=seed)
    scores = []
    for _ in range(episodes):
        frame = env.reset()
        state = agent.initial_lstm_state(1)
        stacker = FrameStacker(1, env.frame_shape, cfg.history_length)
        ep_ret = 0.0
        for _ in range(max_steps):
            a, state = agent.act(stacker.push(frame[None]), state, eval_mode=True)
            ts = env.step(int(a[0]))
            frame = ts.obs
            ep_ret += ts.reward
            if ts.terminal or ts.truncated:
                if ts.info and "episode_return" in ts.info:
                    ep_ret = float(ts.info["episode_return"])
                break
        scores.append(ep_ret)
    arr = np.asarray(scores, np.float64)
    return {
        "episodes": episodes,
        "score_mean": float(arr.mean()),
        "score_median": float(np.median(arr)),
        "score_min": float(arr.min()),
        "score_max": float(arr.max()),
    }


def train_r2d2(cfg: Config, max_frames: Optional[int] = None) -> Dict[str, Any]:
    if cfg.replay_ratio > 1:
        raise ValueError(
            "replay_ratio > 1 (clipped replay reuse) is implemented for the "
            "single-process and apex IQN loops; sequence-batch reuse under "
            "LSTM state is the recorded ROADMAP follow-up")
    total_frames = max_frames or cfg.t_max
    lanes = cfg.num_envs_per_actor
    env = make_vector_env(cfg.env_id, lanes, seed=cfg.seed)
    agent = R2D2Agent(
        cfg, env.num_actions, env.frame_shape, jax.random.PRNGKey(cfg.seed)
    )

    seq_total = cfg.r2d2_burn_in + cfg.r2d2_seq_len
    memory = SequenceReplay(
        capacity=max(cfg.memory_capacity // seq_total, 64),
        seq_len=seq_total,
        frame_shape=env.frame_shape,
        lstm_size=agent.core.stored_width,
        lanes=lanes,
        stride=max(seq_total - cfg.r2d2_overlap, 1),
        priority_exponent=cfg.priority_exponent,
        priority_eps=cfg.priority_eps,
        seed=cfg.seed,
    )

    run_dir = os.path.join(cfg.results_dir, cfg.run_id)
    metrics = MetricsLogger(os.path.join(run_dir, "metrics.jsonl"), cfg.run_id)
    ckpt = Checkpointer(os.path.join(cfg.checkpoint_dir, cfg.run_id))
    obs_run = RunObs(cfg, metrics, role="learner")

    frames = 0
    restored = maybe_resume(cfg, ckpt, agent.state)
    if restored is not None:
        agent.state, extra, _ = restored
        frames = int(extra.get("frames", 0))
        agent.key = rng_from_extra(extra, agent.key)
        maybe_restore_replay(cfg, memory)
        metrics.log("resume", step=agent.step, frames=frames)

    obs = env.reset()
    lstm_state = agent.initial_lstm_state(lanes)
    stacker = FrameStacker(lanes, env.frame_shape, cfg.history_length)
    returns: collections.deque = collections.deque(maxlen=100)
    learn_start_seqs = max(cfg.learn_start // seq_total, 8)

    try:
        while frames < total_frames:
            state_c, state_h = (
                np.asarray(x) for x in agent.core.to_stored(lstm_state))
            stacked = stacker.push(obs)  # actor sees the frame-stacked input
            with obs_run.span("act"):
                actions, lstm_state = agent.act(stacked, lstm_state)
            new_obs, rewards, terminals, truncs, ep_returns = env.step(actions)
            cuts = terminals | truncs  # truncation ends the sequence window too
            # the replay stores SINGLE frames; the learn step re-stacks on device
            memory.append_batch(
                obs, actions, rewards, terminals, state_c, state_h, truncations=truncs
            )
            lstm_state = _mask_reset(agent.core, lstm_state, cuts)
            stacker.reset_lanes(cuts)
            obs = new_obs
            frames += lanes
            for r in ep_returns[~np.isnan(ep_returns)]:
                returns.append(float(r))

            if len(memory) >= learn_start_seqs:
                # Cadence normalised to the SAME per-transition reuse as the
                # feedforward path: an IQN step consumes batch_size transitions
                # per frames_per_learn frames; an R2D2 step consumes batch_size
                # sequences x seq_len trained steps, so one learn step per
                # frames_per_learn * seq_len env frames gives identical reuse.
                frames_per_step = cfg.frames_per_learn * cfg.r2d2_seq_len
                steps_due = frames // frames_per_step - agent.step
                for _ in range(max(steps_due, 0)):
                    with obs_run.span("replay_sample"):
                        sample = memory.sample(
                            cfg.batch_size, priority_beta(cfg, frames)
                        )
                    with obs_run.span("learn_step"):
                        info = agent.learn(sample)
                    memory.update_priorities(sample.idx, np.asarray(info["priorities"]))
                    step = agent.step
                    # the priority write-back above already synced on the step's
                    # outputs; a second barrier would be redundant
                    obs_run.after_learn_step(step)
                    if step % cfg.metrics_interval == 0:
                        metrics.log(
                            "learn",
                            step=step,
                            frames=frames,
                            fps=metrics.fps(frames),
                            loss=float(info["loss"]),
                            q_mean=float(info["q_mean"]),
                            mean_return=float(np.mean(returns)) if returns else float("nan"),
                            sequences=len(memory),
                            stem_from_frames_share=stem_from_frames_share(
                                cfg, env.frame_shape),
                        )
                        obs_run.periodic(step, frames, replay_size=len(memory))
                    if cfg.checkpoint_interval and step % cfg.checkpoint_interval == 0:
                        ckpt.save(step, agent.state, {"frames": frames})
                        save_replay_snapshot(cfg, memory)

    finally:
        obs_run.close(agent.step, frames)
    final_eval = evaluate_r2d2(cfg, agent, seed=cfg.seed + 977)
    metrics.log("eval", step=agent.step, **final_eval)
    ckpt.save(agent.step, agent.state, {"frames": frames})
    save_replay_snapshot(cfg, memory)
    ckpt.wait()
    metrics.close()
    return {
        "frames": frames,
        "learn_steps": agent.step,
        "sequences": len(memory),
        "train_return_mean": float(np.mean(returns)) if returns else float("nan"),
        **{f"eval_{k}": v for k, v in final_eval.items()},
    }
