"""Mesh-parallel R2D2: the recurrent architecture under the Ape-X topology.

Same shape as parallel/apex.py (SURVEY.md §2 rows 6-8 mapping), with the
recurrent differences:
- actor inference is lane-sharded AND stateful: the per-lane LSTM (c, h)
  lives on the actor mesh, sharded with the lanes, and is carried on-device
  tick to tick (episode cuts zero it via a device-side mask — no per-tick
  host round-trip of the state);
- the host still snapshots the pre-step state each tick (one device->host
  copy) because the sequence replay must store exact states for burn-in
  (Kapturowski et al. stored-state replay);
- the learner runs the sequence learn step dp-sharded (numerics proven equal
  to single-device in tests/test_r2d2_sharding.py);
- weight publish is the same bf16 cross-mesh broadcast.
"""

from __future__ import annotations

import collections
import os
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rainbow_iqn_apex_tpu.agents.agent import FrameStacker
from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.envs import make_vector_env
from rainbow_iqn_apex_tpu.models.cores import make_core
from rainbow_iqn_apex_tpu.obs import RunObs
from rainbow_iqn_apex_tpu.ops.r2d2 import (
    R2D2TrainState,
    SequenceBatch,
    as_actor_input,
    build_r2d2_act_step,
    build_r2d2_learn_step,
    init_r2d2_state,
    stem_from_frames_share,
    to_device_seq_batch,
)
from rainbow_iqn_apex_tpu.parallel.mesh import (
    actor_mesh,
    batch_sharding,
    learner_mesh,
    replicated,
    split_devices,
    traced_under,
)
from rainbow_iqn_apex_tpu.parallel.multihost import (
    global_is_nq,
    host_state,
    lane_put,
    local_rows as _local_rows,
    make_global_is_weights,
    plan_hosts,
    shift_stack,
)
from rainbow_iqn_apex_tpu.parallel.supervisor import TrainSupervisor
from rainbow_iqn_apex_tpu.replay.sequence import SequenceReplay, SequenceSample
from rainbow_iqn_apex_tpu.train import priority_beta
from rainbow_iqn_apex_tpu.utils import faults, hostsync
from rainbow_iqn_apex_tpu.utils.checkpoint import (
    Checkpointer,
    maybe_restore_replay,
    maybe_resume,
    rng_extra,
    rng_from_extra,
)
from rainbow_iqn_apex_tpu.parallel.quant_publish import QuantPublishMixin
from rainbow_iqn_apex_tpu.utils.quantize import wrap_act_quantized
from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger
from rainbow_iqn_apex_tpu.utils.prefetch import BatchPrefetcher
from rainbow_iqn_apex_tpu.utils.writeback import (
    RingCommitter,
    WritebackRing,
    pipeline_gauges,
)


class R2D2ApexDriver(QuantPublishMixin):
    """Recurrent apex driver; the gated quantized publish surface is the
    shared `QuantPublishMixin` (the two drivers must not drift on it)."""

    def __init__(
        self,
        cfg: Config,
        num_actions: int,
        frame_shape: Tuple[int, int],
        lanes: int,
        devices: Optional[Sequence[jax.Device]] = None,
    ):
        self.cfg = cfg
        ldevs, adevs = split_devices(devices, cfg.learner_devices)
        self.lmesh = learner_mesh(ldevs)
        self.amesh = actor_mesh(adevs)
        self.n_actor_devices = len(adevs)
        if lanes % self.n_actor_devices:
            raise ValueError(
                f"lanes {lanes} must divide across {self.n_actor_devices} actor devices"
            )
        rep_l, rep_a = replicated(self.lmesh), replicated(self.amesh)
        lane_sh = batch_sharding(self.amesh, "actor")
        self._multihost = jax.process_count() > 1
        if self._multihost and cfg.learner_devices:
            raise ValueError(
                "multi-host R2D2 apex needs learner_devices=0 (every chip "
                "plays both roles) so the weight publish stays host-local"
            )

        self.key = jax.random.PRNGKey(cfg.seed)
        self.key, k_init = jax.random.split(self.key)
        self._host_step: Optional[int] = None  # host mirror of state.step
        self.state: R2D2TrainState = jax.device_put(
            init_r2d2_state(cfg, num_actions, k_init, frame_shape), rep_l
        )

        self._batch_sh = batch_sharding(self.lmesh, "dp")
        self._learn = jax.jit(
            traced_under(self.lmesh, build_r2d2_learn_step(cfg, num_actions)),
            in_shardings=(rep_l, self._batch_sh, rep_l),
            donate_argnums=0,
        )
        # multi-host: global IS-weight renormalization (shared helper —
        # sequence counts are not lockstep across hosts, so each row's N is
        # its own host's estimate, folded into nq per row)
        self._global_is_weights = make_global_is_weights(self._batch_sh)
        # act: obs + the core's state lane-sharded; params replicated on the
        # actor mesh
        self.core = core = make_core(cfg)
        state_sh = jax.tree.map(
            lambda _: lane_sh, jax.eval_shape(lambda: core.initial_state(1)))
        act_fn = build_r2d2_act_step(cfg, num_actions, use_noise=True)
        self._act = jax.jit(
            act_fn,
            in_shardings=(rep_a, lane_sh, state_sh, rep_a),
            out_shardings=(lane_sh, lane_sh, state_sh),
        )
        # device-resident frame stacking (shared shift with ApexDriver): the
        # host ships ONE [L, H, W] frame per tick; cut lanes are zeroed
        # in-graph before the shift.  Only used when history_length > 1.
        def stack_act(params, stack, frame, keep, lstm_state, key):
            stack = shift_stack(stack, frame, keep)
            a, q, new_state = act_fn(params, stack, lstm_state, key)
            return a, q, new_state, stack

        self._stack_act = jax.jit(
            stack_act,
            in_shardings=(
                rep_a, lane_sh, lane_sh, lane_sh, state_sh, rep_a,
            ),
            out_shardings=(lane_sh, lane_sh, state_sh, lane_sh),
            donate_argnums=1,
        )
        self.actor_stack = None  # created lazily at the first act_frames
        # device-side episode-cut mask for the carried state
        self._mask_state = jax.jit(
            core.reset_lanes,
            in_shardings=(state_sh, lane_sh),
            out_shardings=state_sh,
        )
        if cfg.bf16_weight_sync:
            self._cast = jax.jit(
                lambda p: jax.tree.map(lambda x: x.astype(jnp.bfloat16), p)
            )
            self._uncast = jax.jit(
                lambda p: jax.tree.map(lambda x: x.astype(jnp.float32), p),
                out_shardings=rep_a,
            )
        self._rep_a = rep_a
        self._lane_sh = lane_sh
        self._put_lanes = lane_put(lane_sh)
        self.actor_params = None
        # quantized actor lanes — the shared QuantPublishMixin surface,
        # gated on a replay-drawn calibration batch under a zero LSTM state
        # (the episode-start condition every lane revisits)
        if self._init_quant_publish(cfg, multihost=self._multihost) != "off":
            act_q_fn = wrap_act_quantized(act_fn)
            self._act_q = jax.jit(
                act_q_fn,
                in_shardings=(rep_a, lane_sh, state_sh, rep_a),
                out_shardings=(lane_sh, lane_sh, state_sh),
            )

            def stack_act_q(qparams, stack, frame, keep, lstm_state, key):
                stack = shift_stack(stack, frame, keep)
                a, q, new_state = act_q_fn(qparams, stack, lstm_state, key)
                return a, q, new_state, stack

            self._stack_act_q = jax.jit(
                stack_act_q,
                in_shardings=(
                    rep_a, lane_sh, lane_sh, lane_sh, state_sh,
                    rep_a,
                ),
                out_shardings=(
                    lane_sh, lane_sh, state_sh, lane_sh,
                ),
                donate_argnums=1,
            )
            # the gate runs on the LEARNER mesh copy (plain jit)
            self._gate_act32 = jax.jit(act_fn)
            self._gate_actq = jax.jit(act_q_fn)
        # lanes is the GLOBAL lane count; each host materialises only its
        # local rows (make_array == device_put when single-process)
        self.lstm_state = jax.tree.map(
            lambda x: self._put_lanes(np.zeros(x.shape, np.float32)),
            jax.eval_shape(
                lambda: core.initial_state(lanes // jax.process_count())))
        self.weights_version = 0
        self.actor_weights_version = 0
        self.publish_weights()

    def _next_key(self):
        self.key, k = jax.random.split(self.key)
        return k

    # publish_weights / attach_obs / wants_calibration and the gated
    # quantized broadcast live in QuantPublishMixin (shared with
    # ApexDriver); only the act-signature-shaped hooks are defined here.
    def set_calibration(self, obs_batch: np.ndarray) -> None:
        """Calibration frames ([n, H, W, C], replay-drawn) for the gate;
        compared under a zero LSTM state — the episode-start condition."""
        n = min(len(obs_batch), max(int(self.cfg.quant_calib_batch), 1))
        obs = np.asarray(obs_batch[:n], np.uint8)
        self._calib_obs = jnp.asarray(obs)
        self._calib_state = self.core.initial_state(n)

    def _gate_actions(self, params, qparams):
        a32, _, _ = self._gate_act32(
            params, self._calib_obs, self._calib_state, self._gate_key)
        aq, _, _ = self._gate_actq(
            qparams, self._calib_obs, self._calib_state, self._gate_key)
        return a32, aq

    def load_state(self, state, extra: Optional[Dict[str, Any]] = None) -> None:
        """Place a restored R2D2TrainState onto the learner mesh, pick up
        the saved RNG stream when present, re-publish actor weights.  The
        weight-version counter resumes from the checkpoint (same fence
        contract as ApexDriver.load_state)."""
        self.state = jax.device_put(state, replicated(self.lmesh))
        self.key = jnp.asarray(rng_from_extra(extra or {}, self.key))
        saved = int((extra or {}).get("weights_version", 0))
        self.weights_version = max(self.weights_version, saved)
        self.publish_weights()

    def restore(self, ckpt) -> Dict[str, Any]:
        """Load the latest checkpoint into the learner mesh and re-publish
        actor weights; returns the checkpoint's extra metadata."""
        state, extra = ckpt.restore(self.state)
        self.load_state(state, extra)
        return extra

    def load_snapshot(self, state, key) -> None:
        """NaN-guard rollback (parallel/supervisor.py); actor params stay as
        last published — the poisoned state never reached them."""
        self.state = jax.device_put(state, replicated(self.lmesh))
        self.key = jnp.asarray(key)

    def act(self, obs: np.ndarray) -> Tuple[np.ndarray, Tuple[np.ndarray, np.ndarray]]:
        """obs [L_local, H, W] u8 (history 1) or [L_local, H, W, hist]
        stacked -> (actions [L_local], pre-step host state (c, h)).

        The pre-step state snapshot is what the sequence replay stores.
        Multi-host: this host feeds/reads only its local lane rows; the
        carried LSTM state stays device-resident and lane-sharded over the
        global actor mesh."""
        # the actor->env hand-off (actions) and the stored-state snapshot
        # the sequence replay requires are OBLIGATORY host materializations
        # on the actor half — sanctioned syncs, not learner-hot-path
        # regressions (docs/PERFORMANCE.md inventory)
        act = self._act_q if self._actor_quant else self._act
        if self._multihost:
            with hostsync.sanctioned():
                pre_c, pre_h = (
                    _local_rows(x) for x in self.core.to_stored(self.lstm_state))
            x = self._put_lanes(as_actor_input(obs, self.cfg.history_length))
            a, _q, self.lstm_state = act(
                self.actor_params, x, self.lstm_state, self._next_key()
            )
            with hostsync.sanctioned():
                return _local_rows(a), (pre_c, pre_h)
        with hostsync.sanctioned():
            pre_c, pre_h = (
                np.asarray(x) for x in self.core.to_stored(self.lstm_state))
        x = as_actor_input(obs, self.cfg.history_length)
        a, _q, self.lstm_state = act(
            self.actor_params, x, self.lstm_state, self._next_key()
        )
        with hostsync.sanctioned():
            return np.asarray(a), (pre_c, pre_h)

    def reset_lanes(self, cuts: np.ndarray) -> None:
        keep = self._put_lanes(1.0 - cuts.astype(np.float32))
        self.lstm_state = self._mask_state(self.lstm_state, keep)

    def act_frames(
        self, frames: np.ndarray, prev_cuts: np.ndarray
    ) -> Tuple[np.ndarray, Tuple[np.ndarray, np.ndarray]]:
        """Device-stacked recurrent acting (history_length > 1): push this
        host's newest [L_local, H, W] frames into the device-resident stack
        (zeroing lanes cut LAST tick) and act; returns (actions, pre-step
        LSTM state snapshot) exactly like act().  The LSTM state itself is
        reset separately via reset_lanes (the loop's existing contract)."""
        with hostsync.sanctioned():  # stored-state snapshot (actor half)
            if self._multihost:
                pre_c, pre_h = (
                    _local_rows(x) for x in self.core.to_stored(self.lstm_state))
            else:
                pre_c, pre_h = (
                    np.asarray(x) for x in self.core.to_stored(self.lstm_state))
        if self.actor_stack is None:
            h, w = frames.shape[1], frames.shape[2]
            self.actor_stack = self._put_lanes(
                np.zeros((frames.shape[0], h, w, self.cfg.history_length), np.uint8)
            )
        keep = self._put_lanes((~np.asarray(prev_cuts, bool)).astype(np.uint8))
        stack_act = self._stack_act_q if self._actor_quant else self._stack_act
        a, _q, self.lstm_state, self.actor_stack = stack_act(
            self.actor_params,
            self.actor_stack,
            self._put_lanes(np.asarray(frames, np.uint8)),
            keep,
            self.lstm_state,
            self._next_key(),
        )
        with hostsync.sanctioned():  # obligatory actor->env hand-off
            if self._multihost:
                return _local_rows(a), (pre_c, pre_h)
            return np.asarray(a), (pre_c, pre_h)

    def learn_batch(self, batch: SequenceBatch) -> Dict[str, Any]:
        """Dispatch one sequence learn step; ``info`` stays DEVICE arrays
        (async dispatch) — the write-back ring decides when to sync."""
        self._state, info = self._learn(self._state, batch, self._next_key())
        if self._host_step is not None:
            self._host_step += 1
        return info

    def learn_local(
        self, sample, global_size: int, beta: float
    ) -> Dict[str, Any]:
        """Sequence learn step fed from this host's local sub-batch; IS
        weights re-derived over the assembled GLOBAL batch exactly as in
        ApexDriver.learn_local (fixed per-host quota => uniform host
        mixture: q(i) = prob_local(i) / n_hosts).  ``priorities`` stay the
        GLOBAL device array — the ring's ``priorities_to_host`` hook
        (multihost.local_rows) extracts this host's rows at retirement."""
        put = lambda x, dt: jax.make_array_from_process_local_data(  # noqa: E731
            self._batch_sh, np.ascontiguousarray(x, dt)
        )
        nq = put(global_is_nq(sample.prob, global_size), np.float32)
        weight = self._global_is_weights(nq, jnp.float32(beta))
        batch = SequenceBatch(
            obs=put(sample.obs, np.uint8),
            action=put(sample.action, np.int32),
            reward=put(sample.reward, np.float32),
            done=put(sample.done, bool),
            valid=put(sample.valid, bool),
            init_c=put(sample.init_c, np.float32),
            init_h=put(sample.init_h, np.float32),
            weight=weight,
        )
        return self.learn_batch(batch)

    # `state` invalidates the host step mirror on direct assignment;
    # learn_batch bypasses the setter and increments it (same contract as
    # ApexDriver) so per-step `driver.step` reads never touch the device.
    @property
    def state(self) -> R2D2TrainState:
        return self._state

    @state.setter
    def state(self, value: R2D2TrainState) -> None:
        self._state = value
        self._host_step = None

    @property
    def step(self) -> int:
        if self._host_step is None:
            with hostsync.sanctioned():
                self._host_step = int(np.asarray(self._state.step))
        return self._host_step


def _eval_r2d2_learner(cfg: Config, env, driver: "R2D2ApexDriver") -> Dict[str, Any]:
    """Evaluate the learner's current params on a single-device eval agent."""
    from rainbow_iqn_apex_tpu.train_r2d2 import R2D2Agent, evaluate_r2d2

    eval_agent = R2D2Agent(
        cfg, env.num_actions, env.frame_shape, jax.random.PRNGKey(cfg.seed + 1),
        train=False,
    )
    eval_agent.state = jax.device_put(host_state(driver.state), jax.local_devices()[0])
    return evaluate_r2d2(cfg, eval_agent, seed=cfg.seed + 977)


def _eval_r2d2_multigame(cfg: Config, spec, env, driver: "R2D2ApexDriver",
                         metrics, step: int, games_obs) -> Dict[str, Any]:
    """Per-game r2d2 eval (docs/MULTITASK.md): the generalist net evaluated
    on each game's own padded env — one `eval` row per game (keyed by
    ``game``) plus the `eval_mt` human-normalized aggregate, the same
    emission contract as the iqn apex driver."""
    from rainbow_iqn_apex_tpu.envs import make_env
    from rainbow_iqn_apex_tpu.eval import human_normalized
    from rainbow_iqn_apex_tpu.multitask.eval import aggregate_human_normalized
    from rainbow_iqn_apex_tpu.multitask.lanes import GameLaneEnv
    from rainbow_iqn_apex_tpu.train_r2d2 import R2D2Agent, evaluate_r2d2

    eval_agent = R2D2Agent(
        cfg, env.num_actions, env.frame_shape,
        jax.random.PRNGKey(cfg.seed + 1), train=False,
    )
    eval_agent.state = jax.device_put(
        host_state(driver.state), jax.local_devices()[0])
    per_game: Dict[str, Dict[str, Any]] = {}
    per_game_hn: Dict[str, Any] = {}
    for g, name in enumerate(spec.games):
        game_env = GameLaneEnv(
            make_env(name, seed=cfg.seed + 977 + g), spec, g)
        try:
            row = evaluate_r2d2(
                cfg, eval_agent, seed=cfg.seed + 977 + g, env=game_env)
        finally:
            game_env.close()  # per-eval envs must not leak (ALE handles)
        hn = human_normalized(name, row["score_mean"])
        per_game_hn[name] = hn
        if hn is not None:
            row["human_normalized"] = hn
        per_game[name] = row
        if metrics is not None:
            metrics.log("eval", step=step, game=name, **row)
    agg = aggregate_human_normalized(per_game_hn)
    score_mean = float(np.mean([r["score_mean"] for r in per_game.values()]))
    if metrics is not None:
        metrics.log("eval_mt", step=step, score_mean=score_mean,
                    games=len(per_game), **agg)
    games_obs.note_eval({"games": per_game})
    return {"score_mean": score_mean, **agg}


def train_apex_r2d2(cfg: Config, max_frames: Optional[int] = None) -> Dict[str, Any]:
    """Mesh-parallel R2D2 Ape-X; multi-host exactly like apex.train_apex
    (same SPMD shape: local lanes/replay/sub-batches, global collectives).

    One recurrent-specific wrinkle: sequence EMISSION times depend on
    episode ends, so ``len(memory)`` is NOT lockstep-deterministic across
    hosts — the multi-host learn trigger therefore uses only the global
    frame counter (after enough ticks every lane has emitted at least one
    full window deterministically)."""
    if cfg.replay_ratio > 1:
        raise ValueError(
            "replay_ratio > 1 (clipped replay reuse) is implemented for the "
            "IQN apex/single loops; sequence-batch reuse under stored LSTM "
            "state is the recorded ROADMAP follow-up")
    total_frames = max_frames or cfg.t_max
    lanes_total = cfg.num_actors * cfg.num_envs_per_actor
    seq_total = cfg.r2d2_burn_in + cfg.r2d2_seq_len
    plan = plan_hosts(cfg, lanes_total)
    multihost, nproc = plan.multihost, plan.nproc
    lanes, lane_lo = plan.lanes, plan.lane_lo
    is_main, local_batch = plan.is_main, plan.local_batch

    # multi-game r2d2 (multitask/; docs/MULTITASK.md): per-game lane blocks
    # + per-game eval/obs rows around ONE generalist recurrent net (padded
    # suite-common frames/actions; GameLaneEnv maps out-of-range actions).
    # Task conditioning and per-game replay shards are the iqn apex
    # driver's — the sequence replay stays one prioritized tree, with
    # per-game learn-share attribution via the slot lane stamps.
    from rainbow_iqn_apex_tpu.multitask.spec import MultiGameSpec

    spec = MultiGameSpec.from_config(cfg)
    if spec is not None and multihost:
        raise ValueError(
            "multi-game apex (cfg.games) is single-host for now — per-host "
            "game partitioning of an SPMD pod is the ROADMAP follow-up")
    games_obs = games_of_lane = None
    mt_learn_rows = None
    if spec is not None:
        from rainbow_iqn_apex_tpu.multitask.lanes import (
            build_game_lanes,
            lane_games,
        )
        from rainbow_iqn_apex_tpu.multitask.obs import GamesObs

        if lanes % spec.num_games:
            raise ValueError(
                f"total lanes {lanes} must divide across "
                f"{spec.num_games} games")
        env = build_game_lanes(
            spec, lanes // spec.num_games, seed=cfg.seed + lane_lo)
        games_obs = GamesObs(spec)
        games_of_lane = lane_games(spec, lanes // spec.num_games)
        mt_learn_rows = np.zeros(spec.num_games, np.int64)
    else:
        env = make_vector_env(cfg.env_id, lanes, seed=cfg.seed + lane_lo)
    driver = R2D2ApexDriver(cfg, env.num_actions, env.frame_shape, lanes_total)

    memory = SequenceReplay(
        capacity=max(cfg.memory_capacity // (seq_total * nproc), 64),
        seq_len=seq_total,
        frame_shape=env.frame_shape,
        lstm_size=driver.core.stored_width,
        lanes=lanes,
        stride=max(seq_total - cfg.r2d2_overlap, 1),
        priority_exponent=cfg.priority_exponent,
        priority_eps=cfg.priority_eps,
        seed=cfg.seed + lane_lo,
    )
    run_dir = os.path.join(cfg.results_dir, cfg.run_id)
    metrics = MetricsLogger(
        os.path.join(run_dir, "metrics.jsonl") if is_main else None,
        cfg.run_id,
        echo=is_main,
        host=cfg.process_id,
    )
    ckpt = Checkpointer(os.path.join(cfg.checkpoint_dir, cfg.run_id))
    faults.install_from(cfg)
    obs_run = RunObs(cfg, metrics, role="learner")
    sup = TrainSupervisor(cfg, metrics=metrics, registry=obs_run.registry)
    # pipeline tracing — identical contract to train_apex (the two drivers
    # must not drift on the obs surface): always-on lag attribution, 1-in-N
    # span sampling; the r2d2 trace unit for appends is the EMITTED sequence
    from rainbow_iqn_apex_tpu.obs.pipeline_trace import PipelineTracer

    ptrace = PipelineTracer(
        metrics, obs_run.registry, cfg.trace_sample_every,
        host=cfg.process_id,
    )
    ptrace.max_weight_lag = cfg.max_weight_lag
    memory.attach_tracer(ptrace)
    driver.attach_obs(metrics, obs_run.registry, tracer=ptrace)
    if driver.quant_disabled_reason is not None:
        metrics.log("notice", event="quant_fallback_multihost",
                    reason="multihost: fp32/bf16 publish path retained")
    # lease + staleness-fence wiring, identical to train_apex (the two
    # drivers must not drift on the elastic surface — docs/RESILIENCE.md)
    from rainbow_iqn_apex_tpu.parallel.elastic import (
        HeartbeatMonitor,
        HeartbeatWriter,
        StalenessFence,
        heartbeat_dir,
        next_lease_epoch,
    )

    heartbeat = monitor = None
    if cfg.heartbeat_interval_s > 0:
        heartbeat = HeartbeatWriter(
            heartbeat_dir(cfg), cfg.process_id, cfg.heartbeat_interval_s,
            role="apex_r2d2", shard=cfg.process_id,
            epoch=next_lease_epoch(heartbeat_dir(cfg), cfg.process_id),
        )
        if spec is not None:
            # lease payloads carry the game set (same contract as apex.py)
            heartbeat.update_payload(game=",".join(spec.games))
        heartbeat.set_weight_version(driver.weights_version)
        heartbeat.start()
        if is_main:
            monitor = HeartbeatMonitor(
                heartbeat_dir(cfg), cfg.heartbeat_timeout_s,
                self_id=cfg.process_id,
            )
    fence = StalenessFence(
        cfg.max_weight_lag, metrics=metrics, registry=obs_run.registry
    )

    # device-resident sample frontier over the sequence tree (same contract
    # as train_apex — the two drivers must not drift on the sampling
    # surface): draws + IS weights in HBM, host gather via the pusher,
    # write-back retiring into the mirror, cold-path reconcile at drains
    frontier = None
    if cfg.device_sampling and cfg.sample_ahead_depth > 0:
        if multihost:
            metrics.log("notice", event="device_sampling_fallback",
                        reason="multihost: host sampling path retained")
        else:
            from rainbow_iqn_apex_tpu.replay.frontier import (
                DeviceSampleFrontier,
            )

            frontier = DeviceSampleFrontier.from_sequence(
                memory, registry=obs_run.registry, seed=cfg.seed + 31
            )

    frames = 0
    last_pub = 0
    restored = maybe_resume(cfg, ckpt, driver.state)
    if restored is not None:
        state, extra, _ = restored
        driver.load_state(state, extra)
        frames = int(extra.get("frames", 0))
        last_pub = driver.step
        maybe_restore_replay(cfg, memory)
        metrics.log("resume", step=driver.step, frames=frames)

    obs = env.reset()
    # device-resident stacking replaces the host FrameStacker whenever the
    # recurrent net takes stacked input (history_length == 1 feeds raw
    # frames and needs neither)
    use_dstack = cfg.device_frame_stack and cfg.history_length > 1
    stacker = None if use_dstack else FrameStacker(
        lanes, env.frame_shape, cfg.history_length
    )
    prev_cuts = np.zeros(lanes, bool)
    returns: collections.deque = collections.deque(maxlen=100)
    prefetcher: Optional[BatchPrefetcher] = None
    # pipelined priority write-back + deferred in-graph NaN guard — the same
    # zero-sync hot path as train_apex (utils/writeback.py; the two drivers
    # must not drift on the learner-throughput surface, which is why the
    # commit/quarantine/drain protocol is the shared RingCommitter)
    ring = WritebackRing(
        cfg.writeback_depth,
        registry=obs_run.registry,
        priorities_to_host=_local_rows if multihost else None,
        materialize_priorities=frontier is None,
        tracer=ptrace,
    )
    committer = RingCommitter(
        ring,
        frontier.update if frontier is not None else memory.update_priorities,
        sup,
        driver.load_snapshot,
        on_drain=frontier.reconcile if frontier is not None else None,
    )
    last_scalars = committer.scalars
    _commit, _drain = committer.commit, committer.drain

    learn_start_seqs = max(cfg.learn_start // seq_total, 8)  # single-host gate
    frames_per_step = cfg.frames_per_learn * cfg.r2d2_seq_len
    # multi-host learn trigger: frames-only (lockstep-deterministic), and
    # counted from THIS (re)start so a resume with a cold/torn replay
    # snapshot re-warms instead of sampling an empty buffer; by this many
    # fresh global frames every lane has emitted >= 1 full window
    frames_warm = max(cfg.learn_start, (seq_total + 1) * lanes_total)
    frames_at_start = frames

    try:
        while frames < total_frames:
            # causal tracing: ticks feeding the NEXT emitted sequence share
            # its trace id (sequence builders span many ticks)
            tick_tid = ptrace.maybe_trace("a", memory.emit_count + 1)
            with ptrace.span("act", tick_tid):
                if use_dstack:
                    with obs_run.span("act"):
                        actions, (pre_c, pre_h) = driver.act_frames(obs, prev_cuts)
                else:
                    with obs_run.span("act"):
                        actions, (pre_c, pre_h) = driver.act(stacker.push(obs))
            with ptrace.span("env_step", tick_tid):
                new_obs, rewards, terminals, truncs, ep_returns = env.step(
                    actions)
            cuts = terminals | truncs
            with ptrace.span("append", tick_tid):
                memory.append_batch(
                    obs, actions, rewards, terminals, pre_c, pre_h, truncations=truncs
                )
            driver.reset_lanes(cuts)
            if not use_dstack:
                stacker.reset_lanes(cuts)
            prev_cuts = cuts
            obs = new_obs
            frames += lanes_total  # global frames: hosts tick in lockstep
            for r in ep_returns[~np.isnan(ep_returns)]:
                returns.append(float(r))

            warm = (
                frames - frames_at_start >= frames_warm
                if multihost
                else len(memory) >= learn_start_seqs
            )
            if warm:
                if driver.wants_calibration():
                    # calibration from replay statistics: the first
                    # history_length consecutive frames of each sampled
                    # sequence, stacked into the act input shape (paired
                    # with the zero LSTM state the gate compares under).
                    # serve_quantize-on only, so the off-mode sampler RNG
                    # stream is untouched.
                    calib = memory.sample(
                        min(cfg.quant_calib_batch, cfg.batch_size),
                        priority_beta(cfg, frames),
                    )
                    h = min(cfg.history_length, calib.obs.shape[1])
                    driver.set_calibration(
                        np.moveaxis(calib.obs[:, :h, :, :, 0], 1, -1))
                if frontier is not None and prefetcher is None:
                    from rainbow_iqn_apex_tpu.utils.prefetch import (
                        SampleAheadPusher,
                    )

                    prefetcher = SampleAheadPusher(
                        frontier,
                        lambda idx, w: (
                            idx,
                            to_device_seq_batch(memory.assemble_idx(idx, w)),
                        ),
                        cfg.batch_size,
                        lambda: priority_beta(cfg, frames),
                        lambda: len(memory),
                        depth=cfg.sample_ahead_depth,
                        registry=obs_run.registry,
                    )
                elif cfg.prefetch_depth > 0 and prefetcher is None:
                    if multihost:
                        # host-side local sample only; the collective-bearing
                        # learn_local stays on the main thread
                        prefetcher = BatchPrefetcher(
                            lambda: (
                                (s := memory.sample(
                                    local_batch, priority_beta(cfg, frames)
                                )).idx,
                                s,
                            ),
                            depth=cfg.prefetch_depth,
                            device_put=False,
                            registry=obs_run.registry,
                        )
                    else:
                        prefetcher = BatchPrefetcher(
                            lambda: (
                                (s := memory.sample(
                                    cfg.batch_size, priority_beta(cfg, frames)
                                )).idx,
                                to_device_seq_batch(s),
                            ),
                            depth=cfg.prefetch_depth,
                            device_put=False,
                            registry=obs_run.registry,
                        )
                steps_due = frames // frames_per_step - driver.step
                for _ in range(max(steps_due, 0)):
                    if sup.snapshot_due(driver.step):
                        # drain first: the rollback target must never hold
                        # a step whose finiteness is still in flight
                        if not _drain():
                            continue
                        sup.snapshot_if_due(
                            driver.step,
                            lambda: (host_state(driver.state), driver.key),
                        )
                    ltid = ptrace.maybe_trace("l", driver.step + 1)
                    if multihost:
                        with ptrace.span("gather", ltid):
                            if prefetcher is not None:
                                idx, s = prefetcher.get()
                            else:
                                s = memory.sample(local_batch, priority_beta(cfg, frames))
                                idx = s.idx
                        links = ptrace.link_ids(
                            "a", memory.trace_ids(idx)) if ltid else ()
                        with ptrace.span("learn_step", ltid, links=links,
                                         step=driver.step + 1):
                            with obs_run.span("learn_step"):
                                info = driver.learn_local(
                                    sup.poison_maybe(s),
                                    global_size=len(memory) * nproc,
                                    beta=priority_beta(cfg, frames),
                                )
                    elif prefetcher is not None:
                        with ptrace.span("gather", ltid):
                            idx, batch = prefetcher.get()
                        # stamps read at dispatch, not the worker's sample —
                        # a lapped slot links one emit late; accepted for
                        # sampled telemetry (see apex.py's note)
                        links = ptrace.link_ids(
                            "a", memory.trace_ids(idx)) if ltid else ()
                        with ptrace.span("learn_step", ltid, links=links,
                                         step=driver.step + 1):
                            with obs_run.span("learn_step"):
                                info = driver.learn_batch(sup.poison_maybe(batch))
                    else:
                        with ptrace.span("replay_sample", ltid):
                            with obs_run.span("replay_sample"):
                                s = memory.sample(
                                    local_batch, priority_beta(cfg, frames)
                                )
                        idx, batch = s.idx, to_device_seq_batch(s)
                        links = ptrace.link_ids(
                            "a", memory.trace_ids(idx)) if ltid else ()
                        with ptrace.span("learn_step", ltid, links=links,
                                         step=driver.step + 1):
                            with obs_run.span("learn_step"):
                                info = driver.learn_batch(sup.poison_maybe(batch))
                    sup.maybe_stall()
                    if mt_learn_rows is not None:
                        # per-game learn share off the sequence slot lane
                        # stamps (telemetry; the `games` row reports it)
                        mt_learn_rows += np.bincount(
                            games_of_lane[memory.lane_of(idx)],
                            minlength=spec.num_games,
                        ).astype(np.int64)
                    # dispatch-only hot path; the deferred guard decision is
                    # still lockstep across hosts (all-reduced loss -> same
                    # in-graph finite flag), same argument as apex.py
                    if not _commit(ring.push(driver.step, idx, info)):
                        continue
                    step = driver.step
                    obs_run.after_learn_step(step)
                    if step - last_pub >= cfg.weight_publish_interval:
                        # ring boundary: actors never adopt params with an
                        # unverified step in their history
                        if not _drain():
                            continue
                        with obs_run.span("publish_weights"):
                            version = driver.publish_weights()
                        last_pub = step
                        obs_run.registry.gauge(
                            "weights_version", "learner"
                        ).set(version)
                        if heartbeat is not None:
                            heartbeat.set_weight_version(version)
                    if step % cfg.metrics_interval == 0:
                        fence.observe(
                            driver.actor_weights_version,
                            driver.weights_version,
                            step=step,
                        )
                        metrics.log(
                            "learn",
                            step=step,
                            frames=frames,
                            fps=metrics.fps(frames),
                            loss=last_scalars.get("loss", float("nan")),
                            q_mean=last_scalars.get("q_mean", float("nan")),
                            mean_return=float(np.mean(returns)) if returns else float("nan"),
                            sequences=len(memory),
                            staleness=step - last_pub,
                            stem_from_frames_share=stem_from_frames_share(
                                cfg, env.frame_shape, driver.lmesh.size),
                        )
                        obs_run.periodic(
                            step,
                            frames,
                            replay_size=len(memory),
                            replay_occupancy=round(
                                len(memory) / max(memory.capacity, 1), 4
                            ),
                            weight_staleness=step - last_pub,
                            weights_version=driver.weights_version,
                            weight_version_lag=fence.lag,
                            **pipeline_gauges(ring, obs_run.registry, frontier),
                        )
                        if spec is not None:
                            # per-game breakdown (the same `games` row the
                            # iqn apex driver emits; sequence replay is one
                            # tree, so per-game sizes come off the slot
                            # lane stamps instead of shard blocks).
                            # Occupancy is each game's fill of its FAIR
                            # SHARE (capacity / num_games) so the number
                            # means the same thing as the iqn driver's
                            # per-game-capacity fill: a balanced full
                            # buffer reads 1.0 per game; > 1.0 says the
                            # game is crowding its siblings out of the
                            # shared tree.
                            sizes = np.bincount(
                                games_of_lane[memory.slot_lanes()],
                                minlength=spec.num_games,
                            ).astype(np.int64)
                            total_rows = max(int(mt_learn_rows.sum()), 1)
                            fair = max(
                                memory.capacity / spec.num_games, 1.0)
                            metrics.log(
                                "games", step=step, frames=frames,
                                schedule="sequence",
                                **games_obs.row(
                                    learn_shares=mt_learn_rows / total_rows,
                                    learn_rows=mt_learn_rows,
                                    game_sizes=sizes,
                                    game_occupancy=sizes / fair,
                                ),
                            )
                        ptrace.emit_lag_row(step)
                        if monitor is not None:
                            # same lease-edge reporting as train_apex: one
                            # host_dead/host_alive row per lease epoch
                            dead, alive = monitor.poll()
                            for lease in dead:
                                metrics.log(
                                    "fault", event="host_dead",
                                    dead_host=lease.host, epoch=lease.epoch,
                                    step=step, frames=frames,
                                )
                            for lease in alive:
                                metrics.log(
                                    "host_alive", alive_host=lease.host,
                                    epoch=lease.epoch, step=step,
                                    frames=frames,
                                )
                    if cfg.eval_interval and step % cfg.eval_interval == 0:
                        # drain on EVERY host (lockstep cadence) so a
                        # rollback here can't diverge the pod; the eval
                        # itself stays main-host work
                        if not _drain():  # evaluate only verified params
                            continue
                        if is_main and spec is not None:
                            _eval_r2d2_multigame(
                                cfg, spec, env, driver, metrics, step,
                                games_obs)
                        elif is_main:
                            metrics.log(
                                "eval", step=step,
                                **_eval_r2d2_learner(cfg, env, driver),
                            )
                    if cfg.checkpoint_interval and step % cfg.checkpoint_interval == 0:
                        # collective under jax.distributed: every host joins,
                        # the primary writes (a p0-only call would hang);
                        # retry decisions are deterministic -> lockstep
                        if not _drain():  # checkpoint only verified params
                            continue
                        sup.save_checkpoint(
                            ckpt, step, host_state(driver.state),
                            {"frames": frames, "weights_version": driver.weights_version,
                             **rng_extra(driver.key)},
                        )
                        sup.save_replay(cfg, memory)
        # end of run: retire the in-flight tail before the final eval/save
        _drain()
    finally:
        if prefetcher is not None:
            prefetcher.close()
        sup.close()
        obs_run.close(driver.step, frames)
        if heartbeat is not None:
            heartbeat.stop()

    if is_main and spec is not None:
        final_eval = _eval_r2d2_multigame(
            cfg, spec, env, driver, metrics, driver.step, games_obs)
    elif is_main:
        final_eval = _eval_r2d2_learner(cfg, env, driver)
        metrics.log("eval", step=driver.step, **final_eval)
    else:
        final_eval = {}
    sup.save_checkpoint(
        ckpt, driver.step, host_state(driver.state),
        {"frames": frames, "weights_version": driver.weights_version,
                             **rng_extra(driver.key)}, critical=True,
    )
    if frontier is not None:
        # the final drain may have been skipped by a rollback: catch the
        # cold-path tree up before it is persisted
        frontier.reconcile()
    sup.save_replay(cfg, memory, critical=True)
    ckpt.wait()
    metrics.close()
    return {
        "frames": frames,
        "learn_steps": driver.step,
        "lanes": lanes_total,
        "sequences": len(memory),
        "train_return_mean": float(np.mean(returns)) if returns else float("nan"),
        "rollbacks": sup.rollbacks,
        "stalls": sup.stalls,
        "io_faults": sup.io_faults,
        **{f"eval_{k}": v for k, v in final_eval.items()},
    }
