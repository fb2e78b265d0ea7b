"""Fused R2D2 Anakin: recurrent actor + env + stored-state sequence replay +
sequence learner, ALL inside one scanned XLA graph.

The recurrent twin of train_anakin.train_anakin_fused — same Podracer/Anakin
topology (the reference's actor+learner+Redis loop, SURVEY.md §3.1-3.2,
collapsed into a single jitted program), with the transition ring replaced by
the HBM sequence ring (replay/device_sequence.py) and the frame-stack actor
replaced by the LSTM actor threading (c, h) through the scan carry.

Semantics pinned to the host R2D2 trainer (train_r2d2.py):
  - the actor sees frame-stacked input AND an LSTM; the replay stores single
    frames + the PRE-act LSTM state of each step (stored-state replay);
  - LSTM state zero-resets on terminal OR truncation (keep mask);
  - learn cadence: one sequence learn step per frames_per_learn * r2d2_seq_len
    env frames — the same per-transition reuse as the feedforward path —
    expressed statically as `period` ticks per step (or k steps per tick
    when lanes exceed that frame budget);
  - warm gate: filled >= max(learn_start // seq_total, 8) sequences, the
    host trainer's learn_start_seqs rule (and the contract
    build_device_r2d2_learn documents).

Multi-device (`--learner-devices N`): env lanes, LSTM lanes and the sequence
ring shard over a dp mesh — per-shard rings under shard_map (sequence
emission is data-dependent, so each shard owns its cursors), per-shard draws
with psum/pmax-corrected IS weights, GSPMD gradient all-reduce
(replay/device_sequence.build_device_r2d2_learn_sharded).
"""

from __future__ import annotations

import collections
import functools
import math
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.models.cores import (
    make_core,
    state_bytes_per_lane,
)
from rainbow_iqn_apex_tpu.obs import RunObs, device_scopes
from rainbow_iqn_apex_tpu.ops.r2d2 import (
    build_r2d2_act_step,
    init_r2d2_state,
    stem_from_frames_share,
)
from rainbow_iqn_apex_tpu.parallel.multihost import shift_stack
from rainbow_iqn_apex_tpu.replay.device_sequence import (
    DeviceSeqState,
    DeviceSequenceReplay,
    build_device_r2d2_learn,
)
from rainbow_iqn_apex_tpu.train import priority_beta
from rainbow_iqn_apex_tpu.utils.checkpoint import Checkpointer, maybe_resume
from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger


def _seq_geometry(cfg: Config):
    """(seq_total, stride, capacity, learn_start_seqs) — host-trainer parity
    (train_r2d2.train_r2d2)."""
    seq_total = cfg.r2d2_burn_in + cfg.r2d2_seq_len
    stride = max(seq_total - cfg.r2d2_overlap, 1)
    capacity = max(cfg.memory_capacity // seq_total, 64)
    learn_start_seqs = max(cfg.learn_start // seq_total, 8)
    return seq_total, stride, capacity, learn_start_seqs


def _learn_cadence(cfg: Config):
    """Static (period_ticks, learns_per_tick) for the in-graph cadence:
    one learn step per frames_per_learn * r2d2_seq_len env frames."""
    fps = cfg.frames_per_learn * cfg.r2d2_seq_len
    lanes = cfg.num_envs_per_actor
    if fps % lanes == 0:
        return fps // lanes, 1
    if lanes % fps == 0:
        return 1, lanes // fps
    # suggest the nearest valid lane counts (ADVICE r3: the reference's
    # cadence is a free parameter; make the constraint cheap to satisfy)
    valid = sorted(
        {d for d in range(1, max(fps, lanes) * 2 + 1)
         if fps % d == 0 or d % fps == 0}
    )
    below = max((d for d in valid if d < lanes), default=None)
    above = min((d for d in valid if d > lanes), default=None)
    near = " or ".join(str(d) for d in (below, above) if d is not None)
    raise ValueError(
        f"fused R2D2 anakin needs lanes ({lanes}) and frames_per_learn * "
        f"r2d2_seq_len ({fps}) to divide one another — the learn cadence "
        f"is compiled into the graph.  Nearest valid --num-envs-per-actor: "
        f"{near}"
    )


def build_fused_r2d2_segment(cfg: Config, game, replay: DeviceSequenceReplay,
                             learn_fn, append_fn=None):
    """Jitted (carry, key) -> (carry, outs) scanning anakin_segment_ticks of
    shift_stack -> recurrent act -> env.step -> sequence append -> gated
    learn.  carry = (ts, ss, env_states, ep_returns, stack, frame, keep,
    core_state, frames); outs = per-tick (ep_return [L], loss/q_mean/
    grad_norm [learns_per_tick], then one entry per counter of the core's
    `stat_names`; NaN when cold or off-cadence; after them one scalar a tick
    per counter of the core's `act_stat_names`, what the tick's own act step
    sowed, NaN where it did not).

    `append_fn` defaults to replay.append; the sharded path passes the
    shard_map'd build_sharded_seq_append so each device's lanes emit into
    their own ring."""
    from rainbow_iqn_apex_tpu.envs.device_games import batched_reset_step

    lanes = cfg.num_envs_per_actor
    period, lpt = _learn_cadence(cfg)
    _, _, _, learn_start_seqs = _seq_geometry(cfg)
    act_fn = build_r2d2_act_step(cfg, game.num_actions, use_noise=True,
                                 with_stats=True)
    env_step = batched_reset_step(game)
    append = append_fn or replay.append
    bw = cfg.priority_weight
    core = make_core(cfg)
    out_names = ("loss", "q_mean", "grad_norm") + tuple(core.stat_names)

    def tick(carry, k):
        ts, ss, env_s, ep, stack, frame, keep, state, frames = carry
        ka, ks, kl = jax.random.split(k, 3)
        # stored-state replay keeps (what the core stores of) the PRE-act state
        pre_c, pre_h = core.to_stored(state)
        with jax.named_scope(device_scopes.TICK_ACT):
            stack = shift_stack(stack, frame, keep)
            actions, _q, state, act_stats = act_fn(
                ts.params, stack, state, ka)
        with jax.named_scope(device_scopes.TICK_ENV):
            env_s, ep, nframe, reward, term, trunc, out_ret = env_step(
                env_s, ep, actions, ks
            )
        with jax.named_scope(device_scopes.TICK_APPEND):
            ss = append(ss, frame, actions, reward, term, trunc, pre_c, pre_h)
        frames = frames + lanes

        # warm gate (sum/min are shard-aware: filled is [n_dev] when the
        # ring is stacked-sharded, a scalar otherwise) + static cadence
        warm = (jnp.sum(ss.filled) >= learn_start_seqs) & (
            jnp.min(ss.filled) >= 1
        )
        due = (frames // lanes) % period == 0
        beta = jnp.float32(
            bw + (1.0 - bw) * jnp.minimum(frames / float(cfg.t_max), 1.0)
        )

        def do_learn(args):
            ts, ss = args

            def one(cr, kk):
                ts, ss = cr
                ts, ss, info = learn_fn(ts, ss, kk, beta)
                return (ts, ss), tuple(info[n] for n in out_names)

            (ts, ss), infos = jax.lax.scan(
                one, (ts, ss), jax.random.split(kl, lpt)
            )
            return ts, ss, infos

        def no_learn(args):
            ts, ss = args
            nanv = jnp.full((lpt,), jnp.nan, jnp.float32)
            return ts, ss, (nanv,) * len(out_names)

        with jax.named_scope(device_scopes.TICK_LEARN):
            ts, ss, infos = jax.lax.cond(
                warm & due, do_learn, no_learn, (ts, ss))

        cut_keep = (~(term | trunc)).astype(jnp.uint8)
        state = core.reset_lanes(state, cut_keep)  # episode cut
        acted = tuple(act_stats.get(n, jnp.float32(jnp.nan))
                      for n in core.act_stat_names)
        return (ts, ss, env_s, ep, stack, nframe, cut_keep, state,
                frames), (out_ret, *infos, *acted)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def segment(carry, key):
        return jax.lax.scan(
            tick, carry, jax.random.split(key, cfg.anakin_segment_ticks)
        )

    return segment


def init_fused_r2d2_carry(cfg: Config, game, ts, ss, key, frames: int = 0):
    from rainbow_iqn_apex_tpu.envs.device_games import batched_init

    lanes = cfg.num_envs_per_actor
    h, w = game.frame_shape
    env_s = batched_init(game, key, lanes)
    ep = jnp.zeros(lanes)
    stack = jnp.zeros((lanes, h, w, cfg.history_length), jnp.uint8)
    frame = jax.vmap(game.render)(env_s)
    keep = jnp.ones(lanes, jnp.uint8)
    state = make_core(cfg).initial_state(lanes)
    return (ts, ss, env_s, ep, stack, frame, keep, state, jnp.int32(frames))


def build_fused_r2d2_eval(cfg: Config, game, episodes: int,
                          max_ticks: int = 1024):
    """In-graph recurrent evaluation: greedy LSTM lanes on the shared rollout
    core, state zero-reset on cut via the rollout's keep mask (the recurrent
    analog of train_anakin.build_fused_eval)."""
    from rainbow_iqn_apex_tpu.envs.device_games import build_rollout

    act_fn = build_r2d2_act_step(cfg, game.num_actions,
                                 use_noise=cfg.eval_noisy)

    def action_fn(params, states, stack, key, lstm):
        a, _q, lstm = act_fn(params, stack, lstm, key)
        return a, lstm

    return build_rollout(game, action_fn, episodes, max_ticks,
                         history=cfg.history_length,
                         actor_init=make_core(cfg).initial_state)


def _replay_snapshot_path(cfg: Config) -> str:
    return os.path.join(cfg.checkpoint_dir, cfg.run_id, "replay_anakin_r2d2.npz")


def _save_replay(cfg: Config, ss: DeviceSeqState) -> None:
    if not cfg.snapshot_replay:
        return
    from rainbow_iqn_apex_tpu.replay import snapshot_io

    host = jax.device_get(ss)
    snapshot_io.atomic_savez(
        _replay_snapshot_path(cfg),
        **{f: getattr(host, f) for f in DeviceSeqState._fields},
    )


def _maybe_restore_replay(cfg: Config, ss: DeviceSeqState) -> DeviceSeqState:
    path = _replay_snapshot_path(cfg)
    if not (cfg.snapshot_replay and os.path.exists(path)):
        return ss
    from rainbow_iqn_apex_tpu.replay import snapshot_io

    z = snapshot_io.load(path)
    # a field the snapshot predates (emit_ticks) keeps its fresh value
    got = {f: z[f] for f in DeviceSeqState._fields if f in z.files}
    for f in ("frames", "buf_frames"):
        # a snapshot from before frames were stored flat holds [..., L, H, W]:
        # the same bytes in the same order, so it is reshaped and taken
        want = getattr(ss, f).shape
        if (f in got and got[f].shape[:len(want) - 1] == want[:-1]
                and got[f].size == math.prod(want)):
            got[f] = got[f].reshape(want)
    if any(v.shape != getattr(ss, f).shape for f, v in got.items()):
        return ss  # geometry change: degrade to cold replay (host-path rule)
    return ss._replace(**{f: jnp.asarray(v) for f, v in got.items()})


def train_anakin_r2d2(cfg: Config,
                      max_frames: Optional[int] = None) -> Dict[str, Any]:
    """R2D2 Anakin: HBM sequence replay either fully fused (jaxgame:* envs,
    the flagship) or host-fed (any Env — the lag-one loop of
    train_anakin.train_anakin with an LSTM actor)."""
    from rainbow_iqn_apex_tpu.envs.device_games import (
        make_device_game,
        tick_budget,
    )

    if cfg.replay_ratio > 1:
        raise ValueError(
            "replay_ratio > 1 (clipped replay reuse) is implemented for the "
            "single-process and apex IQN loops; the fused anakin R2D2 "
            "learner rejects it (ROADMAP follow-up)")
    if not (cfg.fused_env and cfg.env_id.startswith("jaxgame:")):
        return _train_anakin_r2d2_hostfed(cfg, max_frames)
    total_frames = max_frames or cfg.t_max
    lanes = cfg.num_envs_per_actor
    T = cfg.anakin_segment_ticks
    game_name = cfg.env_id.split(":", 1)[1]
    game = make_device_game(game_name, cfg.device_game_tick_cap)
    h, w = game.frame_shape
    seq_total, stride, capacity, _ = _seq_geometry(cfg)
    _learn_cadence(cfg)  # validate divisibility before building anything
    core = make_core(cfg)

    key = jax.random.PRNGKey(cfg.seed)
    key, k_init, k_env = jax.random.split(key, 3)
    ts = init_r2d2_state(cfg, game.num_actions, k_init, frame_shape=(h, w))

    n_dev = cfg.learner_devices if cfg.learner_devices > 0 else len(jax.devices())
    if n_dev > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from rainbow_iqn_apex_tpu.replay.device_sequence import (
            build_device_r2d2_learn_sharded,
            build_sharded_seq_append,
            device_seq_shardings,
            stack_seq_shards,
        )

        if lanes % n_dev or cfg.batch_size % n_dev or capacity % n_dev:
            raise ValueError(
                f"fused R2D2 anakin over {n_dev} devices needs lanes "
                f"({lanes}), batch ({cfg.batch_size}) and sequence capacity "
                f"({capacity}) divisible by the device count"
            )
        mesh = Mesh(np.array(jax.devices()[:n_dev]), ("dp",))
        local_replay = DeviceSequenceReplay(
            capacity=capacity // n_dev, seq_len=seq_total,
            frame_shape=(h, w), lstm_size=core.stored_width,
            lanes=lanes // n_dev, stride=stride,
            priority_exponent=cfg.priority_exponent,
            priority_eps=cfg.priority_eps,
        )
        replay = local_replay
        learn_fn = build_device_r2d2_learn_sharded(
            cfg, game.num_actions, local_replay, mesh
        )
        append_fn = build_sharded_seq_append(local_replay, mesh)
        # born sharded (see train_anakin_fused): never whole on the first chip
        ss0 = jax.jit(
            lambda: stack_seq_shards(local_replay.init_state(), n_dev),
            out_shardings=device_seq_shardings(mesh),
        )()
        _lane = NamedSharding(mesh, P("dp"))
        _rep = NamedSharding(mesh, P())

        def place(carry):
            ts, ss, env_s, ep, stack, frame, keep, state, frames = carry
            lane_tree = jax.tree.map(
                lambda x: jax.device_put(x, _lane),
                (env_s, ep, stack, frame, keep, state),
            )
            return (
                jax.device_put(ts, _rep),
                jax.device_put(ss, device_seq_shardings(mesh)),
                *lane_tree,
                jax.device_put(frames, _rep),
            )
    else:
        replay = DeviceSequenceReplay(
            capacity=capacity, seq_len=seq_total, frame_shape=(h, w),
            lstm_size=core.stored_width, lanes=lanes, stride=stride,
            priority_exponent=cfg.priority_exponent,
            priority_eps=cfg.priority_eps,
        )
        learn_fn = build_device_r2d2_learn(cfg, game.num_actions, replay)
        append_fn = None
        ss0 = replay.init_state()
        place = lambda carry: carry  # noqa: E731

    segment = build_fused_r2d2_segment(cfg, game, replay, learn_fn, append_fn)

    run_dir = os.path.join(cfg.results_dir, cfg.run_id)
    metrics = MetricsLogger(os.path.join(run_dir, "metrics.jsonl"), cfg.run_id)
    ckpt = Checkpointer(os.path.join(cfg.checkpoint_dir, cfg.run_id))
    obs_run = RunObs(cfg, metrics, role="learner")

    frames = 0
    ss = ss0
    restored = maybe_resume(cfg, ckpt, ts)
    if restored is not None:
        ts, extra, _ = restored
        frames = int(extra.get("frames", 0))
        ss = _maybe_restore_replay(cfg, ss)
        metrics.log("resume", step=int(ts.step), frames=frames)
    learn_steps = int(ts.step)

    carry = place(init_fused_r2d2_carry(cfg, game, ts, ss, k_env, frames))

    eval_fn = build_fused_r2d2_eval(
        cfg, game, cfg.eval_episodes, max_ticks=tick_budget(game_name, 1024)
    )

    def run_eval(params, step_no: int) -> Dict[str, Any]:
        from rainbow_iqn_apex_tpu.train_anakin import fused_eval_scores

        k = jax.random.fold_in(jax.random.PRNGKey(cfg.seed + 977), step_no)
        return fused_eval_scores(eval_fn, params, k)

    returns: collections.deque = collections.deque(maxlen=100)

    def crossed(interval: int, before: int, after: int) -> bool:
        return interval > 0 and before // interval != after // interval

    def emit_ticks_of(ss: DeviceSeqState) -> float:
        """Ticks on which append's conditional ran its emit branch (mean of
        the shards' counters over a mesh); read only when a row is logged."""
        return float(np.mean(np.asarray(ss.emit_ticks)))

    def nanmean(x) -> float:
        x = np.asarray(x)
        return float(np.nanmean(x)) if np.any(~np.isnan(x)) else float("nan")

    row_emit_ticks, row_frames = emit_ticks_of(ss), frames
    state_bytes = state_bytes_per_lane(core)

    # --trace-dir: the capture's 'device_time' row names the segment's work
    # by scope from the compiled text (no compile: the program has run)
    obs_run.trace_window.add_program(
        lambda: segment.lower(carry, k).compile().as_text())
    try:
        while frames < total_frames:
            key, k = jax.random.split(key)
            with obs_run.span("segment", ticks=T):
                carry, (out_ret, loss, q_mean, grad_norm, *counters) = segment(
                    carry, k)
                ts, ss = carry[0], carry[1]
                frames += T * lanes
                prev_steps = learn_steps
                learn_steps = int(ts.step)
            # units: steps_per_sec counts learn steps, not segments
            obs_run.after_learn_step(learn_steps, units=learn_steps - prev_steps)
            for r in np.asarray(out_ret)[~np.isnan(np.asarray(out_ret))]:
                returns.append(float(r))

            if crossed(cfg.metrics_interval, prev_steps, learn_steps):
                emit_ticks = emit_ticks_of(ss)
                emit_share = (emit_ticks - row_emit_ticks) / (
                    (frames - row_frames) // lanes)
                row_emit_ticks, row_frames = emit_ticks, frames
                metrics.log(
                    "learn",
                    step=learn_steps,
                    frames=frames,
                    fps=metrics.fps(frames),
                    loss=nanmean(loss),
                    q_mean=nanmean(q_mean),
                    grad_norm=nanmean(grad_norm),
                    mean_return=float(np.mean(returns)) if returns else float("nan"),
                    append_emit_tick_share=emit_share,
                    core_state_bytes_per_lane=state_bytes,
                    stem_from_frames_share=stem_from_frames_share(
                        cfg, (h, w), n_dev),
                    # the learn steps' counters, then the ticks' own (the
                    # mean over this dispatch's ticks)
                    **{n: nanmean(v) for n, v in zip(
                        (*core.stat_names, *core.act_stat_names), counters)},
                )
                obs_run.periodic(learn_steps, frames)
            if crossed(cfg.eval_interval, prev_steps, learn_steps):
                metrics.log("eval", step=learn_steps,
                            **run_eval(carry[0].params, learn_steps))
            if crossed(cfg.checkpoint_interval, prev_steps, learn_steps):
                ckpt.save(learn_steps, ts, {"frames": frames})
                _save_replay(cfg, ss)

    finally:
        obs_run.close(learn_steps, frames)
    final_eval = run_eval(carry[0].params, learn_steps)
    metrics.log("eval", step=learn_steps, **final_eval)
    ckpt.save(learn_steps, ts, {"frames": frames})
    _save_replay(cfg, ss)
    ckpt.wait()
    metrics.close()
    return {
        "frames": frames,
        "learn_steps": learn_steps,
        "train_return_mean": float(np.mean(returns)) if returns else float("nan"),
        **{f"eval_{k}": v for k, v in final_eval.items()},
    }


def _train_anakin_r2d2_hostfed(cfg: Config,
                               max_frames: Optional[int] = None) -> Dict[str, Any]:
    """Host-fed R2D2 Anakin: env on host, everything else in HBM — sequence
    ring, builders, LSTM state and frame stack all device-resident across
    ticks; per tick the host ships one [L, H, W] frame tensor and reads back
    actions (the exact lag-one staging of train_anakin.train_anakin, with
    the recurrent actor).  This is the trainer real ALE Atari will use once
    ROMs exist (SURVEY.md §2 native-dep row: ALE stays host-side)."""
    from rainbow_iqn_apex_tpu.agents.agent import put_frames
    from rainbow_iqn_apex_tpu.envs import make_vector_env

    total_frames = max_frames or cfg.t_max
    lanes = cfg.num_envs_per_actor
    env = make_vector_env(cfg.env_id, lanes, seed=cfg.seed)
    h, w = env.frame_shape
    seq_total, stride, capacity, learn_start_seqs = _seq_geometry(cfg)
    core = make_core(cfg)
    replay = DeviceSequenceReplay(
        capacity=capacity, seq_len=seq_total, frame_shape=(h, w),
        lstm_size=core.stored_width, lanes=lanes, stride=stride,
        priority_exponent=cfg.priority_exponent,
        priority_eps=cfg.priority_eps,
    )
    key = jax.random.PRNGKey(cfg.seed)
    key, k_init = jax.random.split(key)
    ts = init_r2d2_state(cfg, env.num_actions, k_init, frame_shape=(h, w))
    act_fn = build_r2d2_act_step(cfg, env.num_actions, use_noise=True)

    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def act_append(params, stack, ss, lstm, frame, keep, prev, key):
        """Append LAST tick's completed transition (lag-one: reward/cut are
        only known after env.step), zero-reset cut lanes' stack + LSTM, act.
        Returns the pre-act LSTM state for the NEXT append (stored-state
        replay keeps the state the actor had BEFORE seeing each frame)."""
        if prev is not None:
            ss = replay.append(ss, *prev)
        stack = shift_stack(stack, frame, keep)
        lstm = core.reset_lanes(lstm, keep)
        pre = core.to_stored(lstm)
        a, _q, lstm = act_fn(params, stack, lstm, key)
        return a, stack, ss, lstm, pre

    learn = jax.jit(
        build_device_r2d2_learn(cfg, env.num_actions, replay),
        donate_argnums=(0, 1),
    )

    run_dir = os.path.join(cfg.results_dir, cfg.run_id)
    metrics = MetricsLogger(os.path.join(run_dir, "metrics.jsonl"), cfg.run_id)
    ckpt = Checkpointer(os.path.join(cfg.checkpoint_dir, cfg.run_id))
    obs_run = RunObs(cfg, metrics, role="learner")

    frames = 0
    ss = replay.init_state()
    restored = maybe_resume(cfg, ckpt, ts)
    if restored is not None:
        ts, extra, _ = restored
        frames = int(extra.get("frames", 0))
        ss = _maybe_restore_replay(cfg, ss)
        metrics.log("resume", step=int(ts.step), frames=frames)
    learn_steps = int(ts.step)

    stack = jnp.zeros((lanes, h, w, cfg.history_length), jnp.uint8)
    lstm = core.initial_state(lanes)
    obs = env.reset()
    prev_cuts = np.zeros(lanes, bool)
    prev = None
    returns: collections.deque = collections.deque(maxlen=100)
    device = jax.devices()[0]
    frames_per_step = cfg.frames_per_learn * cfg.r2d2_seq_len
    warm = False  # latches: filled is monotone, so stop syncing once open

    # one eval agent for the whole run (rebuilding it per eval would redo
    # init + jit of the act step every interval)
    from rainbow_iqn_apex_tpu.train_r2d2 import R2D2Agent, evaluate_r2d2

    eval_agent = R2D2Agent(cfg, env.num_actions, env.frame_shape,
                           jax.random.PRNGKey(cfg.seed + 31), train=False)

    def run_eval(ts):
        eval_agent.state = ts
        return evaluate_r2d2(cfg, eval_agent, seed=cfg.seed + 977)

    # --trace-dir: the capture's 'device_time' row resolves both programs'
    # ops to scopes, each in its own text (read when the capture closes, in
    # steady state: `prev` is a tuple then, the program the ticks run)
    obs_run.trace_window.add_program(lambda: act_append.lower(
        ts.params, stack, ss, lstm, frame_d, keep_d, prev, k
    ).compile().as_text())
    obs_run.trace_window.add_program(lambda: learn.lower(
        ts, ss, k, jnp.float32(priority_beta(cfg, frames))
    ).compile().as_text())
    try:
        while frames < total_frames:
            frame_d = put_frames(obs)
            keep_d = jax.device_put((~prev_cuts).astype(np.uint8), device)
            key, k = jax.random.split(key)
            with obs_run.span("act_append"):
                actions_d, stack, ss, lstm, pre = act_append(
                    ts.params, stack, ss, lstm, frame_d, keep_d, prev, k
                )
                actions = np.asarray(actions_d)
            new_obs, rewards, terminals, truncs, ep_returns = env.step(actions)
            prev = (
                frame_d,
                actions_d,
                jax.device_put(rewards.astype(np.float32), device),
                jax.device_put(terminals, device),
                jax.device_put(truncs, device),
                pre[0],
                pre[1],
            )
            prev_cuts = terminals | truncs
            obs = new_obs
            frames += lanes
            for r in ep_returns[~np.isnan(ep_returns)]:
                returns.append(float(r))

            # warm gate on the ring's own sequence count (one scalar readback
            # per tick until it opens — the fused path avoids even this)
            if not warm and int(jax.device_get(ss.filled)) >= learn_start_seqs:
                warm = True
                # cadence counts from the warm-open point: without this, the
                # first tick would owe ~learn_start/frames_per_step catch-up
                # steps against a minimally-filled ring (heavy early sample
                # reuse, ADVICE r3) — the fused path's static cadence has no
                # such burst, and A/B parity with it matters more than parity
                # with train_r2d2's cold-start spike.  Both counters are
                # latched so a resumed run (restored frames/learn_steps) keeps
                # its cadence instead of stalling against the old totals.
                warm_open_frames = frames
                warm_open_steps = learn_steps
            if warm:
                steps_due = ((frames - warm_open_frames) // frames_per_step
                             - (learn_steps - warm_open_steps))
                for _ in range(max(steps_due, 0)):
                    key, k = jax.random.split(key)
                    with obs_run.span("learn_step"):
                        ts, ss, info = learn(
                            ts, ss, k, jnp.float32(priority_beta(cfg, frames))
                        )
                    learn_steps += 1
                    # no block_on (see train_anakin.py): keep the dispatch async
                    obs_run.after_learn_step(learn_steps)
                    if learn_steps % cfg.metrics_interval == 0:
                        metrics.log(
                            "learn", step=learn_steps, frames=frames,
                            fps=metrics.fps(frames), loss=float(info["loss"]),
                            q_mean=float(info["q_mean"]),
                            grad_norm=float(info["grad_norm"]),
                            mean_return=float(np.mean(returns))
                            if returns else float("nan"),
                            stem_from_frames_share=stem_from_frames_share(
                                cfg, (h, w)),
                        )
                        obs_run.periodic(learn_steps, frames)
                    if cfg.eval_interval and learn_steps % cfg.eval_interval == 0:
                        metrics.log("eval", step=learn_steps, **run_eval(ts))
                    if (cfg.checkpoint_interval
                            and learn_steps % cfg.checkpoint_interval == 0):
                        ckpt.save(learn_steps, ts, {"frames": frames})
                        _save_replay(cfg, ss)

    finally:
        obs_run.close(learn_steps, frames)
    final_eval = run_eval(ts)
    metrics.log("eval", step=learn_steps, **final_eval)
    ckpt.save(learn_steps, ts, {"frames": frames})
    _save_replay(cfg, ss)
    ckpt.wait()
    metrics.close()
    return {
        "frames": frames,
        "learn_steps": learn_steps,
        "train_return_mean": float(np.mean(returns)) if returns else float("nan"),
        **{f"eval_{k}": v for k, v in final_eval.items()},
    }
