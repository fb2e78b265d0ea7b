"""TPU-native Ape-X: the device slice is simultaneously the learner and the
actor fleet.

Parity map (SURVEY.md §2 rows 6-8, §3.1-3.2, §5 "Distributed communication
backend"; north star BASELINE.json:5):

  reference (PyTorch + Redis)            this module (JAX/XLA)
  -----------------------------------    -----------------------------------
  1 learner process on GPU               learn step jit-sharded over the
                                         learner mesh axis "dp" (batch split,
                                         params replicated, gradient
                                         all-reduce inserted by XLA over ICI)
  N actor processes on CPUs              batched vector-env lanes, inference
                                         jit-sharded lane-wise over the actor
                                         mesh axis "actor"
  Redis experience append (TCP)          host-DRAM sharded replay append
  Redis batch fetch + priority write     local shard sample + write-back
  Redis weight mailbox (~10MB fp32)      device_put of bf16 params from the
                                         learner mesh to the actor mesh
                                         (one ICI broadcast per publish)
  actor-side initial priorities          n-step TD estimate from the actor's
  (Ape-X paper §3)                       own Q outputs, no extra forward pass

Single-host multi-device SPMD; multi-host (jax.distributed over DCN) reuses
the same code with per-host replay shards — the shard topology is already
host-aligned.
"""

from __future__ import annotations

import collections
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rainbow_iqn_apex_tpu.agents.agent import (
    FrameStacker,
    put_frames,
    to_device_batch,
)
from rainbow_iqn_apex_tpu.utils.prefetch import BatchPrefetcher, make_replay_prefetcher
from rainbow_iqn_apex_tpu.utils import hostsync
from rainbow_iqn_apex_tpu.utils.writeback import (
    RingCommitter,
    WritebackRing,
    cadence_hit,
    check_reuse_cadences,
    pipeline_gauges,
    reuse_health,
    reuse_learn_row,
)
from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.envs import make_vector_env
from rainbow_iqn_apex_tpu.obs import RunObs
from rainbow_iqn_apex_tpu.ops.learn import (
    Batch,
    TrainState,
    build_act_step,
    build_learn_step,
    init_train_state,
)
from rainbow_iqn_apex_tpu.parallel.mesh import (
    actor_mesh,
    batch_sharding,
    learner_mesh,
    replicated,
    split_devices,
)
from rainbow_iqn_apex_tpu.parallel.quant_publish import QuantPublishMixin
from rainbow_iqn_apex_tpu.parallel.sharded_replay import ShardedReplay
from rainbow_iqn_apex_tpu.parallel.supervisor import TrainSupervisor
from rainbow_iqn_apex_tpu.utils import faults
from rainbow_iqn_apex_tpu.utils.quantize import wrap_act_quantized
from rainbow_iqn_apex_tpu.utils.checkpoint import (
    Checkpointer,
    maybe_restore_replay,
    maybe_resume,
    rng_extra,
    rng_from_extra,
)
from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger


from rainbow_iqn_apex_tpu.parallel.multihost import (  # noqa: E402
    global_is_nq,
    host_state,
    lane_put,
    local_rows as _local_rows,
    make_global_is_weights,
    plan_hosts,
    shift_stack,
)


class ActorPriorityEstimator:
    """Ape-X actor-side initial priorities from the actor's own Q outputs.

    Buffers n+1 ticks of (Q(s, a_sel), reward, terminal) per lane; when the
    replay completes the transition started n ticks ago, emits
        |R_n + gamma^n * maxQ(s_now) * alive - Q(s_then, a_then)|
    with the same truncate-at-terminal rules the replay applies.
    """

    def __init__(self, lanes: int, n_step: int, gamma: float):
        self.n = n_step
        self.gamma = gamma
        self.q_sel = collections.deque(maxlen=n_step + 1)  # each [L]
        self.rew = collections.deque(maxlen=n_step + 1)
        self.term = collections.deque(maxlen=n_step + 1)

    def push(
        self,
        q_values: np.ndarray,  # [L, A] actor Q estimates at s_t
        actions: np.ndarray,  # [L]
        rewards: np.ndarray,  # [L] r_t
        terminals: np.ndarray,  # [L] d_t
    ) -> Optional[np.ndarray]:
        L = actions.shape[0]
        self.q_sel.append(q_values[np.arange(L), actions])
        self.rew.append(rewards.astype(np.float32))
        self.term.append(terminals.astype(bool))
        if len(self.rew) <= self.n:
            return None
        # window ticks: t-n .. t-1 rewards, bootstrap at t
        r = np.stack(list(self.rew))[:-1]  # [n, L] == r_{t-n..t-1}
        d = np.stack(list(self.term))[:-1]  # [n, L]
        alive = np.cumprod(1.0 - d[:-1].astype(np.float32), axis=0)
        alive = np.concatenate([np.ones((1, L), np.float32), alive], axis=0)
        gammas = self.gamma ** np.arange(self.n, dtype=np.float32)
        rn = (r * alive * gammas[:, None]).sum(axis=0)
        no_done = 1.0 - d.any(axis=0).astype(np.float32)
        boot = (self.gamma**self.n) * q_values.max(axis=1) * no_done
        return np.abs(rn + boot - self.q_sel[0]).astype(np.float64)


class ApexDriver(QuantPublishMixin):
    """Owns meshes, sharded compute fns, and the stale actor-param copy.

    The gated quantized publish surface (publish_weights, attach_obs,
    calibration handshake, quant/publish rows) is the shared
    `QuantPublishMixin` — the two apex drivers must not drift on it."""

    def __init__(
        self,
        cfg: Config,
        num_actions: int,
        devices: Optional[Sequence[jax.Device]] = None,
        state_shape: Optional[Tuple[int, ...]] = None,
        spec=None,  # multitask.MultiGameSpec: task-conditioned multi-game mode
    ):
        self.cfg = cfg
        self.num_actions = num_actions
        self.spec = spec
        # replay reuse (ops/learn.py make_reuse_learn_step): one learn
        # dispatch = a fused K-pass executable, so state.step — and the
        # host step mirror — advance K per learn_batch call
        self.reuse_k = max(int(cfg.replay_ratio), 1)
        ldevs, adevs = split_devices(devices, cfg.learner_devices)
        self.lmesh = learner_mesh(ldevs)
        self.amesh = actor_mesh(adevs)
        self.n_actor_devices = len(adevs)

        rep_l, rep_a = replicated(self.lmesh), replicated(self.amesh)
        self._rep_l = rep_l  # league retune rebuilds the learn jit in place
        self.key = jax.random.PRNGKey(cfg.seed)
        self.key, k_init = jax.random.split(self.key)
        if spec is not None:
            # task-conditioned learner (multitask/; docs/MULTITASK.md):
            # MultiGameIQN with a game-id embedding, ONE jitted dispatch for
            # the whole suite — game ids are data, shapes are suite-common,
            # so XLA compiles once per role regardless of how many games run
            from rainbow_iqn_apex_tpu.multitask.ops import (
                build_mt_act_step,
                build_mt_learn_step,
                init_mt_train_state,
            )

            state = init_mt_train_state(cfg, spec, k_init)
            learn_fn = build_mt_learn_step(cfg, spec)
            act_fn = build_mt_act_step(cfg, spec, use_noise=True)
        else:
            state = init_train_state(
                cfg, num_actions, k_init, state_shape=state_shape)
            learn_fn = build_learn_step(cfg, num_actions)
            act_fn = build_act_step(cfg, num_actions, use_noise=True)
        self._host_step: Optional[int] = None  # host mirror of state.step
        self.state: TrainState = jax.device_put(state, rep_l)

        # learner step: batch split over dp, state replicated; XLA inserts the
        # gradient all-reduce (psum over "dp") from the sharding alone.
        self._batch_sh = batch_sharding(self.lmesh, "dp")
        self._learn = jax.jit(
            learn_fn,
            in_shardings=(rep_l, self._batch_sh, rep_l),
            donate_argnums=0,
        )
        # actor step: lanes split over the actor mesh, params replicated.
        # Multi-game acting threads a lane-sharded [L] game-id vector
        # (set_lane_games) through the same executable.
        lane_sh = batch_sharding(self.amesh, "actor")
        self._lane_sh = lane_sh
        self._lane_games = None  # device [L] i32, mt mode only

        # device-resident frame stacking: the stack never leaves the actor
        # mesh; the host ships ONE [L, H, W] frame per tick and lanes cut
        # last tick are zeroed in-graph before the shift — bit-identical to
        # the host FrameStacker (tests/test_parallel.py), 4x less transfer,
        # and none of the strided host shifting that was the measured host
        # bottleneck (~14k frames/s on the build sandbox vs ~130k replay
        # append).  One wiring for both act flavours: multi-game threads
        # one extra lane-sharded [L] game-id operand through the same
        # executables (fp32 and quantized twins alike).
        def jit_act_pair(fn):
            game_sh = (lane_sh,) if spec is not None else ()

            def stack_act(params, stack, frame, keep, *rest):
                # rest = (game, key) in multi-game mode, (key,) otherwise
                stack = shift_stack(stack, frame, keep)
                a, q = fn(params, stack, *rest)
                return a, q, stack

            act = jax.jit(
                fn,
                in_shardings=(rep_a, lane_sh, *game_sh, rep_a),
                out_shardings=(lane_sh, lane_sh),
            )
            stack = jax.jit(
                stack_act,
                in_shardings=(
                    rep_a, lane_sh, lane_sh, lane_sh, *game_sh, rep_a),
                out_shardings=(lane_sh, lane_sh, lane_sh),
                donate_argnums=1,
            )
            return act, stack

        self._act, self._stack_act = jit_act_pair(act_fn)
        self._put_lanes = lane_put(lane_sh)
        self.actor_stack = None  # created lazily at the first act_frames
        # quantized actor lanes (utils/quantize.py + the shared
        # QuantPublishMixin; cfg.serve_quantize): publishes ship int8 (4x
        # less ICI/DCN traffic than fp32) and the actor act step
        # dequantizes inside its own executable — guarded by the
        # greedy-action agreement gate on a replay-drawn calibration batch.
        self._rep_a = rep_a
        if self._init_quant_publish(
                cfg, multihost=jax.process_count() > 1) != "off":
            act_q_fn = wrap_act_quantized(act_fn)
            self._act_q, self._stack_act_q = jit_act_pair(act_q_fn)
            # the gate runs on the LEARNER mesh copy (plain jit)
            self._gate_act32 = jax.jit(act_fn)
            self._gate_actq = jax.jit(act_q_fn)
        if cfg.bf16_weight_sync:
            self._cast = jax.jit(
                lambda p: jax.tree.map(lambda x: x.astype(jnp.bfloat16), p)
            )
            self._uncast = jax.jit(
                lambda p: jax.tree.map(lambda x: x.astype(jnp.float32), p),
                out_shardings=rep_a,
            )
        # multi-host: global IS-weight renormalization (shared helper so the
        # two apex drivers can't drift)
        self._global_is_weights = make_global_is_weights(self._batch_sh)
        self.actor_params = None
        # weight-staleness fencing (parallel/elastic.py): every publish
        # stamps a monotonically increasing version so actors — in-process
        # or external (WeightMailbox readers) — can measure their lag in
        # publishes and fence past cfg.max_weight_lag
        self.weights_version = 0
        self.actor_weights_version = 0
        self.publish_weights()  # initial broadcast

    # ------------------------------------------------------------- weight sync
    # publish_weights / attach_obs / wants_calibration and the gated
    # quantized broadcast live in QuantPublishMixin (shared with the r2d2
    # driver); only the act-signature-shaped hooks are defined here.
    def set_lane_games(self, games: np.ndarray) -> None:
        """Multi-game mode: pin the [L] per-lane game ids (lane-sharded
        device constant every act dispatch conditions on).  Must match the
        lane order of `multitask.build_game_lanes`."""
        self._lane_games = self._put_lanes(np.asarray(games, np.int32))

    @property
    def _game_args(self) -> tuple:
        """The extra act-step operand(s): one lane-sharded game-id vector
        in multi-game mode, nothing otherwise — splatted at every act call
        site so the two modes share one call shape."""
        return () if self._lane_games is None else (self._lane_games,)

    def set_calibration(self, obs_batch: np.ndarray,
                        game: Optional[np.ndarray] = None) -> None:
        """Calibration observations for the agreement gate, drawn from
        replay statistics (a sampled batch's stacked obs, plus its game ids
        in multi-game mode).  Clipped to ``cfg.quant_calib_batch`` so the
        gate executables compile once."""
        n = min(len(obs_batch), max(int(self.cfg.quant_calib_batch), 1))
        self._calib_obs = jnp.asarray(np.asarray(obs_batch[:n], np.uint8))
        if self.spec is not None:
            if game is None:
                game = np.zeros(n, np.int32)
            self._calib_game = jnp.asarray(
                np.asarray(game[:n], np.int32))

    def _gate_actions(self, params, qparams):
        calib = (self._calib_obs, *(
            (self._calib_game,) if self.spec is not None else ()))
        a32, _ = self._gate_act32(params, *calib, self._gate_key)
        aq, _ = self._gate_actq(qparams, *calib, self._gate_key)
        return a32, aq

    # ---------------------------------------------------------------- resume
    def load_state(self, state, extra: Optional[Dict[str, Any]] = None) -> None:
        """Place a restored TrainState onto the learner mesh, pick up the
        saved RNG stream when the checkpoint carries one, and re-publish
        actor weights.  The weight-version counter resumes from the
        checkpoint too — a restarted learner must publish versions ABOVE the
        ones out-of-process actors already hold, or the staleness fence's
        lag arithmetic fails open exactly in the restart window."""
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

    # ------------------------------------------------------- league adoption
    def adopt_params(self, host_params) -> None:
        """League exploit adoption (league/member.py, docs/LEAGUE.md):
        replace online AND target params with the copied member's weights
        and re-publish so the actor lanes act on them immediately.  Called
        only at a drained boundary (no unverified step in flight).  Adam
        moments re-init fresh — the loser's statistics are meaningless at
        the winner's point in weight space, and a deterministic re-init is
        reproducible where stale moments are not.  Step counter, PRNG
        stream, and weight-version counter all continue (the version keeps
        rising, so out-of-process staleness fences never see a rollback)."""
        from rainbow_iqn_apex_tpu.league.member import graft_tree
        from rainbow_iqn_apex_tpu.ops.learn import make_optimizer

        params = graft_tree(host_state(self.state).params, host_params)
        params = jax.device_put(params, replicated(self.lmesh))
        self.state = self._state.replace(
            params=params,
            target_params=jax.tree.map(jnp.copy, params),
            opt_state=jax.jit(
                make_optimizer(self.cfg).init,
                out_shardings=self._rep_l)(params),
        )
        self.publish_weights()

    def retune(self, learning_rate: Optional[float] = None) -> None:
        """Mid-run live-gene adoption: rebuild the jitted learn step under
        the new learning rate (one recompile per exploit event — rare by
        construction).  Replay-side genes (n_step, priority_exponent) are
        retuned on the replay by the loop; restart genes (replay_ratio,
        schedule) wait for the next respawn's config overlay."""
        if learning_rate is None:
            return
        self.cfg = self.cfg.replace(learning_rate=float(learning_rate))
        if self.spec is not None:
            from rainbow_iqn_apex_tpu.multitask.ops import build_mt_learn_step

            learn_fn = build_mt_learn_step(self.cfg, self.spec)
        else:
            learn_fn = build_learn_step(self.cfg, self.num_actions)
        self._learn = jax.jit(
            learn_fn,
            in_shardings=(self._rep_l, self._batch_sh, self._rep_l),
            donate_argnums=0,
        )

    # ---------------------------------------------------------------- rollback
    def load_snapshot(self, state, key) -> None:
        """NaN-guard rollback (parallel/supervisor.py): last-good host state
        back onto the learner mesh.  Actor params are NOT re-published — the
        poisoned state was never published (the guard runs before the
        publish), so actors still hold good, merely stale, weights."""
        self.state = jax.device_put(state, replicated(self.lmesh))
        self.key = jnp.asarray(key)

    # ----------------------------------------------------------------- compute
    def _next_key(self):
        self.key, k = jax.random.split(self.key)
        return k

    def act_async(self, stacked_obs: np.ndarray):
        """Dispatch lane-sharded inference; returns DEVICE arrays immediately
        (JAX async dispatch) so the host can overlap env work."""
        act = self._act_q if self._actor_quant else self._act
        return act(self.actor_params, put_frames(stacked_obs),
                   *self._game_args, self._next_key())

    def act(self, stacked_obs: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        a, q = self.act_async(stacked_obs)
        # the actor->env hand-off is an OBLIGATORY host materialization (the
        # vector env lives on host) — a sanctioned sync on the actor half,
        # not a learner-hot-path regression (docs/PERFORMANCE.md inventory)
        with hostsync.sanctioned():
            return np.asarray(a), np.asarray(q)

    def act_frames(
        self, frames: np.ndarray, prev_cuts: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Device-stacked acting: push this host's newest [L_local, H, W]
        frames into the device-resident stack (zeroing lanes whose episode
        was cut LAST tick, matching FrameStacker.reset_lanes ordering) and
        act on the result."""
        if self.actor_stack is None:
            h, w = frames.shape[1], frames.shape[2]
            self.actor_stack = self._put_lanes(
                np.zeros((frames.shape[0], h, w, self.cfg.history_length), np.uint8)
            )
        keep = self._put_lanes((~np.asarray(prev_cuts, bool)).astype(np.uint8))
        stack_act = self._stack_act_q if self._actor_quant else self._stack_act
        a, q, self.actor_stack = stack_act(
            self.actor_params,
            self.actor_stack,
            self._put_lanes(np.asarray(frames, np.uint8)),
            keep,
            *self._game_args,
            self._next_key(),
        )
        with hostsync.sanctioned():  # obligatory actor->env hand-off
            if jax.process_count() > 1:
                return _local_rows(a), _local_rows(q)
            return np.asarray(a), np.asarray(q)

    def learn(self, sample) -> Dict[str, Any]:
        return self.learn_batch(to_device_batch(sample))

    def learn_batch(self, batch: Batch) -> Dict[str, Any]:
        """Dispatch one learn step; ``info`` values stay DEVICE arrays (JAX
        async dispatch) — the write-back ring decides when to sync."""
        self._state, info = self._learn(self._state, batch, self._next_key())
        if self._host_step is not None:
            self._host_step += self.reuse_k
        return info

    # ------------------------------------------------------------- multi-host
    # Every pod host runs this same program (SPMD): each host contributes its
    # LOCAL sub-batch / env lanes, jax assembles the global arrays over the
    # process-spanning mesh, and the only cross-host traffic is the gradient
    # all-reduce XLA inserts (the Redis TCP fabric replaced by ICI/DCN
    # collectives — SURVEY §2 rows 6-7, §5 backend mapping).
    def learn_local(
        self,
        sample,
        global_size: Optional[int] = None,
        beta: Optional[float] = None,
    ) -> Dict[str, Any]:
        """Learn step fed from this host's local sub-batch (B/hosts rows).
        Returns info with ``priorities`` as the GLOBAL dp-sharded device
        array; pass ``multihost.local_rows`` as the write-back ring's
        ``priorities_to_host`` to get this host's rows (input order) at
        retirement.

        IS weights: each host's replay normalizes weights over its OWN
        sub-batch, which is inconsistent across hosts (each host's max row
        gets 1.0 regardless of its true global weight).  When
        ``global_size``/``beta`` are given, weights are re-derived in-graph
        over the assembled GLOBAL batch from the per-row sample
        probabilities: q(i) = prob_local(i) / n_hosts (the fixed per-host
        quota makes the scheme a uniform mixture over hosts), w = (N q)^-b
        max-normalized across all hosts — the cross-host max is one tiny
        XLA collective.
        """
        put = lambda x, dt: jax.make_array_from_process_local_data(  # noqa: E731
            self._batch_sh, np.ascontiguousarray(x, dt)
        )
        if global_size is not None and sample.prob is not None:
            nq = put(global_is_nq(sample.prob, global_size), np.float32)
            weight = self._global_is_weights(nq, jnp.float32(beta))
        else:
            weight = put(sample.weight, np.float32)
        batch = Batch(
            obs=put(sample.obs, np.uint8),
            action=put(sample.action, np.int32),
            reward=put(sample.reward, np.float32),
            next_obs=put(sample.next_obs, np.uint8),
            discount=put(sample.discount, np.float32),
            weight=weight,
        )
        # priorities stay the GLOBAL device array: the write-back ring
        # extracts this host's local rows at RETIREMENT (K steps later) via
        # its priorities_to_host hook, so dispatching a multi-host learn
        # step blocks on nothing either
        return self.learn_batch(batch)

    def act_local(self, stacked_obs: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Lane-sharded inference fed from this host's local lanes."""
        obs = self._put_lanes(stacked_obs)
        act = self._act_q if self._actor_quant else self._act
        a, q = act(self.actor_params, obs, self._next_key())
        with hostsync.sanctioned():  # obligatory actor->env hand-off
            return _local_rows(a), _local_rows(q)

    # `state` invalidates the host step mirror on direct assignment
    # (load_state / load_snapshot / tests); learn_batch bypasses the setter
    # and increments the mirror, so the hot loop's per-step `driver.step`
    # reads never block on the device queue.
    @property
    def state(self) -> TrainState:
        return self._state

    @state.setter
    def state(self, value: TrainState) -> None:
        self._state = value
        self._host_step = None

    @property
    def step(self) -> int:
        if self._host_step is None:
            with hostsync.sanctioned():
                self._host_step = int(np.asarray(self._state.step))
        return self._host_step


def _eval_learner(cfg: Config, env, driver: "ApexDriver") -> Dict[str, Any]:
    """Evaluate the LEARNER's current params (reference evaluates the learner
    checkpoint, SURVEY §3.5) on a single-device eval agent."""
    from rainbow_iqn_apex_tpu.eval import evaluate_state

    return evaluate_state(cfg, env, host_state(driver.state), seed=cfg.seed + 977)


def _eval_multigame(cfg: Config, spec, driver: "ApexDriver",
                    metrics, step: int, games_obs) -> Dict[str, Any]:
    """Multi-game eval emission (docs/MULTITASK.md): one `eval` row PER
    GAME (keyed by ``game``) plus one `eval_mt` aggregate row carrying the
    suite human-normalized median/mean — the Atari-57 reporting convention.
    Returns the flat aggregate dict for the run summary."""
    from rainbow_iqn_apex_tpu.multitask.eval import evaluate_multigame

    res = evaluate_multigame(
        cfg, spec, host_state(driver.state).params, seed=cfg.seed + 977)
    games_obs.note_eval(res)
    if metrics is not None:
        for name, row in res["games"].items():
            metrics.log("eval", step=step, game=name, **row)
        metrics.log(
            "eval_mt", step=step, score_mean=res["score_mean"],
            hn_median=res["hn_median"], hn_mean=res["hn_mean"],
            hn_games=res["hn_games"], games=len(res["games"]),
        )
    return {
        "score_mean": res["score_mean"],
        "hn_median": res["hn_median"],
        "hn_mean": res["hn_mean"],
        "hn_games": res["hn_games"],
    }


def train_apex(cfg: Config, max_frames: Optional[int] = None) -> Dict[str, Any]:
    """The full Ape-X loop on one host's slice (SURVEY §3.1 + §3.2 fused).

    Multi-host (cfg.process_count > 1): every pod host runs this SAME loop in
    lockstep over a process-spanning mesh — each host steps its slice of the
    env lanes, appends to its LOCAL replay shard, and contributes its local
    sub-batch to the dp-sharded learn step; the gradient all-reduce XLA
    inserts over ICI/DCN is the only cross-host traffic (SURVEY §2 rows 6-7:
    the reference's remote Redis actors, re-shaped).  Requires
    learner_devices == 0 (both roles on every chip) so the weight publish
    stays host-local.
    """
    # league membership (league/; docs/LEAGUE.md): validate the league_*
    # spec and overlay this member's genome BEFORE any component reads a
    # hyperparameter (replay_ratio below derives reuse_k from the overlaid
    # cfg).  Default-off takes none of this — `member` stays None and the
    # loop is bitwise the pre-league path (tier-1 asserted).
    from rainbow_iqn_apex_tpu.league.member import LeagueMember
    from rainbow_iqn_apex_tpu.league.population import check_league_config

    check_league_config(cfg)
    member = LeagueMember.from_config(cfg)
    if member is not None:
        # genome n_step must respect the ring geometry (per-shard seg =
        # capacity // lanes regardless of the shard split; members are
        # single-host so the whole capacity/lane space is this process's)
        # or the replay build below crash-loops every respawn
        member.clamp_n_step(
            cfg.memory_capacity
            // (cfg.num_actors * cfg.num_envs_per_actor)
            - cfg.history_length - 1)
        cfg = member.overlay(cfg)
    total_frames = max_frames or cfg.t_max
    lanes_total = cfg.num_actors * cfg.num_envs_per_actor
    plan = plan_hosts(cfg, lanes_total)
    multihost, nproc = plan.multihost, plan.nproc
    lanes, lane_lo = plan.lanes, plan.lane_lo
    is_main, local_batch = plan.is_main, plan.local_batch

    # multi-game mode (multitask/; docs/MULTITASK.md): N games in one pod —
    # per-game lane blocks, a task-conditioned learner, game-pinned replay
    # shards behind the interleave schedule, per-game eval/obs rows.  Unset
    # games (the default) touches NONE of this: the single-game path below
    # is bitwise the pre-multitask loop (tier-1 asserted).
    from rainbow_iqn_apex_tpu.multitask.spec import MultiGameSpec

    spec = MultiGameSpec.from_config(cfg)
    if spec is not None and multihost:
        raise ValueError(
            "multi-game apex (cfg.games) is single-host for now — per-host "
            "game partitioning of an SPMD pod is the ROADMAP follow-up")
    if member is not None and multihost:
        raise ValueError(
            "league members (cfg.league_member_id) are single-host for now "
            "— a member IS one pod's trainer; partitioning one member over "
            "an SPMD pod while the controller swaps its weights mid-run is "
            "the ROADMAP follow-up (docs/LEAGUE.md)")
    games_obs = None
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
    else:
        # per-lane seeds are carved from the GLOBAL lane space so hosts
        # never duplicate env streams
        env = make_vector_env(cfg.env_id, lanes, seed=cfg.seed + lane_lo)
    driver = ApexDriver(
        cfg, env.num_actions,
        state_shape=(*env.frame_shape, cfg.history_length), spec=spec,
    )
    if lanes_total % driver.n_actor_devices:
        raise ValueError(
            f"total lanes {lanes_total} must divide across "
            f"{driver.n_actor_devices} actor devices"
        )
    if spec is not None:
        driver.set_lane_games(lane_games(spec, lanes // spec.num_games))

    if spec is not None:
        from rainbow_iqn_apex_tpu.multitask.replay import MultiGameReplay

        # cfg.replay_shards is PER GAME here: each game owns its own shard
        # block (its per-game priority trees), so one game's drop/readmit
        # never touches a sibling's sampling distribution
        shards = max(cfg.replay_shards, 1) * spec.num_games
        memory = MultiGameReplay.build_games(
            spec,
            max(cfg.replay_shards, 1),
            cfg.memory_capacity,
            lanes,
            schedule=cfg.multitask_schedule,
            history=cfg.history_length,
            n_step=cfg.multi_step,
            gamma=cfg.gamma,
            priority_exponent=cfg.priority_exponent,
            priority_eps=cfg.priority_eps,
            seed=cfg.seed + lane_lo,
            use_native=cfg.use_native_sumtree,
        )
    else:
        shards = cfg.replay_shards // nproc if multihost else cfg.replay_shards
        memory = ShardedReplay.build(
            max(shards, 1),
            cfg.memory_capacity // nproc,
            lanes,
            frame_shape=env.frame_shape,
            history=cfg.history_length,
            n_step=cfg.multi_step,
            gamma=cfg.gamma,
            priority_exponent=cfg.priority_exponent,
            priority_eps=cfg.priority_eps,
            seed=cfg.seed + lane_lo,
            use_native=cfg.use_native_sumtree,
        )
    learn_start = cfg.learn_start // nproc  # local transitions before learning
    import os

    from rainbow_iqn_apex_tpu.train import priority_beta

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
    memory.attach_registry(obs_run.registry)
    # pipeline tracing (obs/pipeline_trace.py): always-on lag attribution
    # (sample age, ring retirement, publish->adopt) + 1-in-N causal span
    # emission when cfg.trace_sample_every > 0 (off = bitwise seed path)
    from rainbow_iqn_apex_tpu.obs.pipeline_trace import PipelineTracer

    ptrace = PipelineTracer(
        metrics, obs_run.registry, cfg.trace_sample_every,
        host=cfg.process_id,
    )
    ptrace.max_weight_lag = cfg.max_weight_lag
    memory.attach_tracer(ptrace)
    driver.attach_obs(metrics, obs_run.registry, tracer=ptrace)
    if driver.quant_disabled_reason is not None:
        # mirrors the device_sampling multihost fallback: identical cfg on
        # every host, so the whole pod declines together (lockstep SPMD)
        metrics.log("notice", event="quant_fallback_multihost",
                    reason="multihost: fp32/bf16 publish path retained")
    # NOTE (multi-host): the injector/retry decisions are pure functions of
    # (spec, seed, call order), identical on every host — supervised control
    # flow can never diverge the SPMD program around a collective.
    sup = TrainSupervisor(cfg, metrics=metrics, registry=obs_run.registry)
    from rainbow_iqn_apex_tpu.parallel.elastic import (
        HeartbeatMonitor,
        HeartbeatWriter,
        StalenessFence,
        heartbeat_dir,
        next_lease_epoch,
    )

    heartbeat = monitor = None
    league_hb = None
    if member is not None:
        member.attach_obs(metrics, obs_run.registry)
        if cfg.heartbeat_interval_s > 0:
            # member lease under the LEAGUE dir (the controller's watch
            # point, distinct from this run's own heartbeat below): the
            # payload carries member id + exploit generation so the
            # controller reads PBT state straight off the lease
            league_hb = HeartbeatWriter(
                os.path.join(cfg.league_dir, "heartbeats"),
                cfg.league_member_id, cfg.heartbeat_interval_s,
                role="member", epoch=member.epoch,
                payload_fn=member.lease_payload,
            ).start()
    if cfg.heartbeat_interval_s > 0:
        heartbeat = HeartbeatWriter(
            heartbeat_dir(cfg), cfg.process_id, cfg.heartbeat_interval_s,
            role="apex", shard=cfg.process_id * max(shards, 1),
            # every (re)start claims a fresh incarnation epoch: a relaunched
            # host's death/revival fires as a NEW transition instead of
            # being deduped against the previous incarnation's report
            epoch=next_lease_epoch(heartbeat_dir(cfg), cfg.process_id),
            # league members stamp member/generation into this run-dir
            # lease too (parallel/elastic.py Lease.member/.generation)
            payload_fn=member.lease_payload if member is not None else None,
        )
        if spec is not None:
            # lease payloads carry the game set this host serves, so an
            # external controller (RoleSupervisor respawns, fence monitors)
            # stays game-aware without tailing this process's JSONL
            heartbeat.update_payload(game=",".join(spec.games))
        heartbeat.set_weight_version(driver.weights_version)
        heartbeat.start()
        if is_main:
            monitor = HeartbeatMonitor(
                heartbeat_dir(cfg), cfg.heartbeat_timeout_s, self_id=cfg.process_id
            )
    # learner failover (parallel/failover.py; docs/RESILIENCE.md "learner
    # failover"): claim this incarnation's learner-role epoch through the
    # same O_EXCL markers standbys race, stamp it into the lease payload
    # (the standby's takeover contract) and arm the zombie publish fence.
    # Default-off takes none of this; multihost declines with a reasoned
    # notice (N pod hosts racing one role claim would fence each other —
    # pod-level failover is a ROADMAP follow-up).
    lfence = None
    learner_epoch = 0
    if cfg.failover_standby:
        if multihost:
            metrics.log("notice", event="failover_fallback",
                        reason="multihost: external respawn loop retained")
        else:
            from rainbow_iqn_apex_tpu.parallel.elastic import EpochFence
            from rainbow_iqn_apex_tpu.parallel.failover import (
                LEARNER_ROLE,
                learner_epoch_at_start,
                refresh_fence,
            )

            learner_epoch = learner_epoch_at_start(cfg)
            lfence = EpochFence(learner_epoch)
            driver.attach_epoch_fence(lfence, learner_epoch)
            if heartbeat is not None:
                heartbeat.update_payload(
                    role=LEARNER_ROLE, learner_epoch=learner_epoch)
                heartbeat.beat()  # visible before the first renewal interval
            metrics.log("failover", event="claim", won=True,
                        epoch=learner_epoch, source="learner_start")

    def _zombie_detected(at_step: int) -> bool:
        """Refresh the learner-epoch fence from the claim markers and answer
        whether a SUCCESSOR epoch has appeared — this incarnation is then a
        zombie and must EXIT, not merely fence its publishes: a fenced loop
        that keeps training burns the device indefinitely and keeps writing
        force=True checkpoints into the same Orbax directory the successor
        owns (two concurrent CheckpointManagers — torn steps, pruning
        races).  Emits the terminal failover row on detection."""
        if lfence is None:
            return False
        refresh_fence(lfence, heartbeat_dir(cfg))
        if lfence.epoch <= learner_epoch:
            return False
        metrics.log("failover", event="zombie_exit", epoch=learner_epoch,
                    fence_epoch=lfence.epoch, step=at_step, frames=frames)
        return True

    zombie = False
    # staleness fence (parallel/elastic.py): the fused loop adopts the
    # published version atomically with the params, so lag is structurally 0
    # here and the fence can never fire — observe() keeps the
    # weight_version_lag gauge live with the same contract out-of-process
    # actors (scripts/chaos_soak.py, WeightMailbox readers) fence on.
    fence = StalenessFence(
        cfg.max_weight_lag, metrics=metrics, registry=obs_run.registry
    )

    # device-resident sample frontier (replay/frontier.py): mirror the shard
    # priority vectors into HBM, draw index batches + IS weights on device,
    # and let the sample-ahead pusher assemble/push — the learner thread
    # never walks a host sum-tree.  Off (or depth 0, or multi-host) keeps
    # the host sampling path bitwise intact.
    frontier = None
    if cfg.device_sampling and cfg.sample_ahead_depth > 0:
        if multihost:
            # per-host mirrors of a dp-sharded global draw are a follow-up;
            # an SPMD pod must not diverge on a per-host capability, so every
            # host falls back together (the cfg is identical on all hosts)
            metrics.log("notice", event="device_sampling_fallback",
                        reason="multihost: host sampling path retained")
        elif member is not None:
            # the HBM priority mirror stages deltas under the n-step window
            # geometry it was built with; a mid-run n-step adoption (a LIVE
            # league gene) would silently desync it — members keep the host
            # sampling path, which `set_n_step` re-fences correctly
            metrics.log(
                "notice", event="device_sampling_fallback",
                reason="league member: host sampling retained (mid-run "
                       "n-step adoption does not compose with the device "
                       "frontier mirror)")
        elif spec is not None and cfg.multitask_schedule != "mass":
            # the frontier's fused HBM draw is proportional to global
            # priority mass — exactly the "mass" schedule and nothing else;
            # per-game-quota schedules need the host interleave
            metrics.log(
                "notice", event="device_sampling_fallback",
                reason="multitask: game-interleaved host sampling retained "
                       "(multitask_schedule=mass composes with the device "
                       "frontier)")
        else:
            from rainbow_iqn_apex_tpu.replay.frontier import (
                DeviceSampleFrontier,
            )

            frontier = DeviceSampleFrontier.from_sharded(
                memory, registry=obs_run.registry, seed=cfg.seed + 31
            )

    # cross-host replay plane (replay/net/): appends, samples and priority
    # write-backs ride the framed-socket transport to disaggregated replay
    # shard servers discovered via leases (docs/RESILIENCE.md).  Default-
    # off; every composition hazard declines with a reasoned notice and
    # keeps the in-process path bitwise intact.
    rplane = None
    if cfg.replay_net_remote:
        if multihost:
            # per-host lane->shard pinning across a pod is a follow-up; an
            # SPMD pod must not diverge on a per-host capability, so every
            # host falls back together
            metrics.log("notice", event="replay_net_fallback",
                        reason="multihost: in-process replay retained")
        elif member is not None:
            metrics.log(
                "notice", event="replay_net_fallback",
                reason="league member: in-process replay retained (a "
                       "mid-run n-step adoption mutates the window "
                       "geometry the remote shards were built with)")
        elif spec is not None:
            # game-major shard blocks pin to servers structurally (a
            # server owns shard_base..+shards, which ARE game blocks),
            # but the learner-side game-quota interleave is a host draw
            # the wire client doesn't reproduce yet
            metrics.log(
                "notice", event="replay_net_fallback",
                reason="multitask: in-process replay retained (wire "
                       "game-quota interleave is a follow-up)")
        elif frontier is not None:
            metrics.log(
                "notice", event="replay_net_fallback",
                reason="device_sampling: the HBM priority mirror needs "
                       "the in-process shard trees")
        elif cfg.serve_quantize != "off":
            metrics.log(
                "notice", event="replay_net_fallback",
                reason="serve_quantize: calibration samples the local "
                       "memory, which stays empty under a remote plane")
        else:
            from rainbow_iqn_apex_tpu.replay.net.plane import (
                RemoteReplayPlane,
            )

            rplane = RemoteReplayPlane.from_config(
                cfg, lanes, metrics=metrics,
                obs_registry=obs_run.registry,
            )
            if lfence is not None:
                # update/snapshot frames carry the learner epoch; the shard
                # servers latch the highest seen and refuse older stamps
                # (the PR-16 step fence grown an epoch dimension)
                rplane.set_learner_epoch(learner_epoch)

    frames = 0
    last_pub = 0
    restored = maybe_resume(cfg, ckpt, driver.state)
    if restored is not None:
        state, extra, _ = restored
        driver.load_state(state, extra)
        frames = int(extra.get("frames", 0))
        last_pub = driver.step
        if rplane is None:
            maybe_restore_replay(cfg, memory)
        # (remote plane: shard servers restore their own snapshots at
        # spawn, fenced by the learner's checkpoint step — nothing local)
        metrics.log("resume", step=driver.step, frames=frames)
        if lfence is not None:
            # successor version floor: the deceased learner may have
            # PUBLISHED versions above its last checkpointed
            # weights_version — start strictly above the highest version
            # any lease ever advertised, so no consumer watches the
            # successor re-issue a version number it already adopted
            peak = max(
                (lease.weight_version for lease in HeartbeatMonitor(
                    heartbeat_dir(cfg), cfg.heartbeat_timeout_s,
                ).leases().values()),
                default=-1,
            )
            if peak > driver.weights_version:
                driver.weights_version = peak
                driver.actor_weights_version = peak
            metrics.log("failover", event="restore", epoch=learner_epoch,
                        step=driver.step, version_floor=max(
                            peak, driver.weights_version))

    estimator = (
        ActorPriorityEstimator(lanes, cfg.multi_step, cfg.gamma)
        if cfg.initial_priority_from_actor
        else None
    )
    obs = env.reset()
    returns: collections.deque = collections.deque(maxlen=100)
    prefetcher: Optional[BatchPrefetcher] = None

    # Pipelined priority write-back (utils/writeback.py): step t's priorities
    # are materialized and written to the replay only while step t+K runs on
    # device, and the NaN/Inf guard reads the in-graph `finite` flag at the
    # same boundary — the steady-state learn loop issues ZERO blocking
    # device->host transfers per step (docs/PERFORMANCE.md).  The commit/
    # quarantine/drain rollback protocol is the shared RingCommitter.
    ring = WritebackRing(
        cfg.writeback_depth,
        registry=obs_run.registry,
        priorities_to_host=_local_rows if multihost else None,
        # mirror mode: retirement hands the still-on-device |TD| array to
        # frontier.update (a jitted scatter) — the priority vector never
        # crosses to host per step; reconcile() syncs the cold path at drains
        materialize_priorities=frontier is None,
        tracer=ptrace,
    )
    if rplane is not None:
        # wire write-back: the ring's retired |TD| rows route to shard
        # servers as batched update frames keyed by GLOBAL slot id — the
        # same id space memory.update_priorities routes on in-process
        _update_target = rplane.update_priorities
    elif frontier is not None and spec is not None:
        # device sampling bypasses memory.update_priorities (the |TD| stays
        # a device array retiring into the HBM mirror), so the per-game
        # learn-share counters the `games` row reports are fed from the
        # host idx vector explicitly
        def _update_target(idx, td_abs, _f=frontier.update):
            memory.note_learn_idx(idx)
            return _f(idx, td_abs)
    elif frontier is not None:
        _update_target = frontier.update
    else:
        _update_target = memory.update_priorities
    if lfence is not None:
        _unfenced_update = _update_target
        _wb_refused = [0]

        def _update_target(idx, td_abs):
            # zombie write-back fence: a superseded learner's retired |TD|
            # rows must not perturb the successor's sampling distribution.
            # One row on the first refusal (a storm is a triage signal, not
            # a log flood — docs/RUNBOOK.md), the fence counts the rest.
            if lfence.stale(learner_epoch):
                _wb_refused[0] += 1
                if _wb_refused[0] == 1:
                    metrics.log("failover", event="fenced_stale",
                                surface="writeback", epoch=learner_epoch,
                                fence_epoch=lfence.epoch)
                return None
            return _unfenced_update(idx, td_abs)
    committer = RingCommitter(
        ring,
        _update_target,
        sup,
        driver.load_snapshot,
        on_drain=(
            frontier.reconcile if frontier is not None
            # drain boundary doubles as write-back flush: every in-flight
            # update frame is acked before a snapshot/publish proceeds
            else rplane.flush_writebacks if rplane is not None
            else None
        ),
    )
    last_scalars = committer.scalars  # newest RETIRED step's host scalars
    _commit, _drain = committer.commit, committer.drain
    # replay reuse (docs/PERFORMANCE.md "Replay reuse"): one sampled batch
    # drives a fused K-pass learn dispatch, so the step counter jumps K per
    # sample — the sample trigger divides steps back into samples, cadences
    # fire on crossings (cadence_hit), and the ring still holds one entry
    # per SAMPLE (final-pass priorities), so priorities lag samples, not
    # passes
    reuse_k = driver.reuse_k
    check_reuse_cadences(cfg, "metrics_interval", "eval_interval",
                         "checkpoint_interval", "guard_snapshot_interval",
                         "weight_publish_interval")

    if multihost and cfg.pipelined_actor:
        raise ValueError("pipelined_actor is single-host only (for now)")
    # multi-host learn trigger: DETERMINISTIC and identical on every host
    # (divergent control flow around a collective deadlocks the pod).  It
    # therefore counts only fresh post-(re)start frames — len(memory) can
    # diverge across hosts when a resume restores replay on some hosts but
    # degrades to cold on one (torn snapshot) — at the cost of re-warming
    # for learn_start frames after every resume.
    frames_at_start = frames
    # device-resident stacking replaces the host FrameStacker on the actor
    # path (pipelined mode keeps the host stacker: its one-tick-lag pipe
    # would need a second in-flight device stack)
    use_dstack = cfg.device_frame_stack and not cfg.pipelined_actor
    stacker = None if use_dstack else FrameStacker(
        lanes, env.frame_shape, cfg.history_length
    )
    prev_cuts = np.zeros(lanes, bool)
    # append seam: one callable serves the pipelined and straight paths —
    # the remote plane spools lane blocks to shard servers, the local path
    # appends in-process.  With the plane active memory.append_ticks stays
    # 0, so actor trace tick ids degenerate to a constant: wire appends
    # are not causally traced yet (accepted; the learn-side links degrade
    # to unlinked spans, nothing breaks).
    _append = memory.append_batch if rplane is None else rplane.append_batch
    pending = None  # pipelined: device (actions, q) dispatched last tick
    held = None  # pipelined: completed transition awaiting its Q for append
    try:
        while frames < total_frames:
            # causal tracing: this tick's appends land on append tick
            # append_ticks+1 — sampled ticks carry act/env-step/append spans
            # under the id the learn span will link back to
            tick_tid = ptrace.maybe_trace("a", memory.append_ticks + 1)
            with ptrace.span("act", tick_tid):
                if use_dstack:
                    with obs_run.span("act"):
                        actions, q = driver.act_frames(obs, prev_cuts)
                else:
                    stacked = stacker.push(obs)
                    if multihost:
                        actions, q = driver.act_local(stacked)
                    elif cfg.pipelined_actor:
                        # Overlap: dispatch inference for THIS obs; execute
                        # the action computed from the PREVIOUS obs
                        # (one-tick behaviour lag; the first tick primes the
                        # pipe synchronously).
                        nxt = driver.act_async(stacked)
                        if pending is None:
                            pending = nxt
                        actions = np.asarray(pending[0])
                    else:
                        actions, q = driver.act(stacked)
            with ptrace.span("env_step", tick_tid):
                new_obs, rewards, terminals, truncs, ep_returns = env.step(
                    actions)
            cuts = terminals | truncs  # truncation cuts windows like a terminal
            if cfg.pipelined_actor:
                # The transition (s_t, a_t, r_t) needs Q(s_t) — that's `nxt`,
                # still computing while the envs stepped. Hold the transition
                # one tick and append it when its Q has certainly landed, so
                # actor-side priorities use the RIGHT observation's values
                # (only the behaviour policy is stale, not the estimates).
                if held is not None:
                    h_obs, h_act, h_rew, h_term, h_trunc, h_q = held
                    pri = (
                        estimator.push(np.asarray(h_q), h_act, h_rew, h_term | h_trunc)
                        if estimator
                        else None
                    )
                    # the held transition lands on THIS tick's append seq
                    # (one append per tick), so tick_tid is its id — the
                    # trace carries the pipeline's own one-tick lag
                    with ptrace.span("append", tick_tid):
                        _append(
                            h_obs, h_act, h_rew, h_term, pri, truncations=h_trunc
                        )
                held = (obs, actions, rewards, terminals, truncs, nxt[1])
                pending = nxt
            else:
                pri = estimator.push(q, actions, rewards, cuts) if estimator else None
                with ptrace.span("append", tick_tid):
                    _append(obs, actions, rewards, terminals, pri, truncations=truncs)
            if not use_dstack:
                stacker.reset_lanes(cuts)
            prev_cuts = cuts
            obs = new_obs
            frames += lanes_total  # global frames: all hosts tick in lockstep
            for r in ep_returns[~np.isnan(ep_returns)]:
                returns.append(float(r))

            if rplane is not None:
                # remote warm-up: the servers' aggregate size/sampleable
                # ride the piggyback state on every reply — no extra RPC
                warm = (rplane.size() >= learn_start
                        and rplane.sampleable())
            else:
                warm = (
                    frames - frames_at_start >= cfg.learn_start
                    if multihost
                    else len(memory) >= learn_start and memory.sampleable
                )
            if warm:
                if driver.wants_calibration():
                    # calibration from replay observation statistics: one
                    # sampled batch's stacked obs (the gate's yardstick —
                    # QuaRL calibrates post-training quantization the same
                    # way).  Only reached with serve_quantize on, so the
                    # off-mode sampler RNG stream is untouched.
                    calib = memory.sample(
                        min(cfg.quant_calib_batch, cfg.batch_size),
                        priority_beta(cfg, frames),
                    )
                    driver.set_calibration(
                        calib.obs, game=getattr(calib, "game", None))
                if rplane is not None and prefetcher is None:
                    # wire sample-ahead: the SampleClient already keeps
                    # `sample_ahead_depth` requests in flight; the shim
                    # only overlaps decode + device_put with the dispatch
                    prefetcher = rplane.make_prefetcher(
                        local_batch,
                        lambda: priority_beta(cfg, frames),
                        to_device_batch,
                        registry=obs_run.registry,
                    )
                elif frontier is not None and prefetcher is None:
                    # sample-ahead pusher: device-drawn index blocks,
                    # host-DRAM frame gather, staged device batches PUSHED
                    # into the bounded queue — the learner only pops
                    from rainbow_iqn_apex_tpu.replay.frontier import (
                        make_batch_assembler,
                    )
                    from rainbow_iqn_apex_tpu.utils.prefetch import (
                        SampleAheadPusher,
                    )

                    prefetcher = SampleAheadPusher(
                        frontier,
                        make_batch_assembler(
                            memory, to_device_batch,
                            registry=obs_run.registry,
                        ),
                        cfg.batch_size,
                        lambda: priority_beta(cfg, frames),
                        lambda: len(memory),
                        # replay reuse: one staged batch feeds K fused
                        # learn passes — the pusher shrinks its queue depth
                        # and device-side draw-ahead K-fold from reuse=
                        depth=cfg.sample_ahead_depth,
                        reuse=reuse_k,
                        registry=obs_run.registry,
                    )
                elif cfg.prefetch_depth > 0 and prefetcher is None:
                    if multihost:
                        # overlap the host-side local sample/assembly with
                        # the device step; the collective-bearing
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
                        prefetcher = make_replay_prefetcher(
                            memory, cfg, lambda: priority_beta(cfg, frames),
                            registry=obs_run.registry,
                        )
                steps_due = (frames // cfg.frames_per_learn
                             - driver.step // reuse_k)
                for _ in range(max(steps_due, 0)):
                    if sup.snapshot_due(driver.step):
                        # drain BEFORE capturing: the snapshot must never
                        # contain a step whose finiteness is still in flight
                        # (it is the rollback target)
                        if not _drain():
                            continue
                        sup.snapshot_if_due(
                            driver.step,
                            lambda: (host_state(driver.state), driver.key),
                        )
                    # causal tracing: the step this dispatch creates; its
                    # span links back to the sampled append ticks its batch
                    # rows came from (env-step -> learn flow arrows)
                    ltid = ptrace.maybe_trace("l", driver.step + 1)
                    if multihost:
                        # local sub-batch in; the global batch assembles
                        # across hosts inside, IS weights are re-derived
                        # globally, and the ring extracts this host's local
                        # priority rows at retirement
                        with ptrace.span("gather", ltid):
                            if prefetcher is not None:
                                idx, sample = prefetcher.get()
                            else:
                                sample = memory.sample(local_batch, priority_beta(cfg, frames))
                                idx = sample.idx
                        links = ptrace.link_ids(
                            "a", memory.trace_ids(idx)) if ltid else ()
                        with ptrace.span("learn_step", ltid, links=links,
                                         step=driver.step + 1):
                            with obs_run.span("learn_step"):
                                info = driver.learn_local(
                                    sup.poison_maybe(sample),
                                    global_size=len(memory) * nproc,
                                    beta=priority_beta(cfg, frames),
                                )
                    elif prefetcher is not None:
                        with ptrace.span("gather", ltid):
                            idx, batch = prefetcher.get()
                        # slot stamps are read at DISPATCH, not at the
                        # worker's sample: a slot the ring cursor lapped in
                        # between (<= lanes*depth/capacity odds per batch)
                        # links one tick late — accepted for sampled
                        # telemetry rather than threading stamps through
                        # every prefetcher payload
                        links = ptrace.link_ids(
                            "a", memory.trace_ids(idx)) if ltid else ()
                        with ptrace.span("learn_step", ltid, links=links,
                                         step=driver.step + 1):
                            with obs_run.span("learn_step"):
                                info = driver.learn_batch(sup.poison_maybe(batch))
                    else:
                        with ptrace.span("replay_sample", ltid):
                            with obs_run.span("replay_sample"):
                                sample = memory.sample(
                                    local_batch, priority_beta(cfg, frames)
                                )
                        idx = sample.idx
                        links = ptrace.link_ids(
                            "a", memory.trace_ids(idx)) if ltid else ()
                        with ptrace.span("learn_step", ltid, links=links,
                                         step=driver.step + 1):
                            with obs_run.span("learn_step"):
                                info = driver.learn(sup.poison_maybe(sample))
                    sup.maybe_stall()
                    # Dispatch-only hot path: info stays on device; the ring
                    # retires step t-K (write-back + deferred NaN guard)
                    # while step t executes.  The guard decision is still
                    # identical on every host — the loss is all-reduced, so
                    # the in-graph finite flag agrees and rollback stays
                    # lockstep (no divergent control flow around a
                    # collective).
                    if not _commit(ring.push(driver.step, idx, info)):
                        continue
                    step = driver.step
                    obs_run.after_learn_step(step, units=reuse_k)
                    if step - last_pub >= cfg.weight_publish_interval:
                        # ring boundary: actors must never adopt params with
                        # an unverified step in their history, so everything
                        # in flight retires (and may roll us back) first
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
                        if member is not None:
                            if (lfence is not None
                                    and lfence.stale(learner_epoch)):
                                # zombie league fence: a superseded member
                                # incarnation must not clobber the
                                # successor's outbox delta chain
                                metrics.log(
                                    "failover", event="fenced_stale",
                                    surface="league", epoch=learner_epoch,
                                    fence_epoch=lfence.epoch)
                            else:
                                # league outbox publish (the int8-delta
                                # chain other members adopt from) rides the
                                # same drained boundary as the actor
                                # broadcast
                                with hostsync.sanctioned():
                                    member.publish(
                                        host_state(driver.state).params,
                                        step=step)
                    if (member is not None
                            and cadence_hit(step, cfg.metrics_interval,
                                            reuse_k)
                            and member.pending()):
                        # exploit adoption at a SAFE drain boundary: every
                        # in-flight step retires (and may roll back) before
                        # the copied weights land; adopt_params republishes
                        # so the actor lanes swap atomically with the
                        # learner
                        if not _drain():
                            continue
                        with hostsync.sanctioned():
                            adopted = member.try_adopt(
                                step, driver.adopt_params, retune=None,
                                max_n_step=memory.max_n_step)
                        if adopted is not None:
                            genome = member.genome
                            driver.retune(
                                learning_rate=genome.learning_rate)
                            memory.set_n_step(genome.n_step)
                            memory.set_priority_exponent(
                                genome.priority_exponent)
                            if estimator is not None:
                                # actor-side priority windows are sized by
                                # n-step: restart the estimator's deques
                                # (it re-primes within n ticks; fresh
                                # appends take the max-priority default
                                # meanwhile, the Ape-X cold-start rule)
                                estimator = ActorPriorityEstimator(
                                    lanes, genome.n_step, cfg.gamma)
                            last_pub = step  # adopt_params republished
                            if heartbeat is not None:
                                heartbeat.set_weight_version(
                                    driver.weights_version)
                    if cadence_hit(step, cfg.metrics_interval, reuse_k):
                        fence.observe(
                            driver.actor_weights_version,
                            driver.weights_version,
                            step=step,
                        )
                        # scalars come from the newest RETIRED step (<= K
                        # behind) — the metric cadence reads host floats the
                        # ring already materialized, never the device queue
                        metrics.log(
                            "learn",
                            step=step,
                            frames=frames,
                            fps=metrics.fps(frames),
                            loss=last_scalars.get("loss", float("nan")),
                            q_mean=last_scalars.get("q_mean", float("nan")),
                            mean_return=float(np.mean(returns)) if returns else float("nan"),
                            staleness=step - last_pub,
                            **reuse_learn_row(reuse_k, last_scalars),
                        )
                        obs_run.periodic(
                            step,
                            frames,
                            replay_size=(
                                rplane.size() if rplane is not None
                                else len(memory)
                            ),
                            # survivors-aware occupancy maintained by
                            # ShardedReplay._observe on this same registry —
                            # recomputing it here would double-count dead
                            # shards in the denominator
                            replay_occupancy=round(
                                obs_run.registry.gauge(
                                    "replay_occupancy", "replay"
                                ).get(), 4,
                            ),
                            weight_staleness=step - last_pub,
                            weights_version=driver.weights_version,
                            weight_version_lag=fence.lag,
                            **pipeline_gauges(
                                ring, obs_run.registry, frontier,
                                reuse=reuse_health(reuse_k, last_scalars),
                            ),
                        )
                        if spec is not None:
                            # per-game breakdown (docs/MULTITASK.md): learn
                            # share, replay occupancy, latest eval score,
                            # human-normalized aggregate — the row obs_report
                            # `games:` and obs/attribution key on
                            metrics.log(
                                "games", step=step, frames=frames,
                                schedule=cfg.multitask_schedule,
                                **games_obs.row(
                                    learn_shares=memory.learn_shares(),
                                    learn_rows=memory.learn_rows_by_game,
                                    sampled_rows=memory.sampled_rows_by_game,
                                    game_sizes=memory.game_sizes(),
                                    game_occupancy=memory.game_occupancy(),
                                    dead_games=memory.dead_games(),
                                ),
                            )
                        # lag-attribution row (obs/pipeline_trace.py):
                        # sample age / retirement / publish->adopt
                        # percentiles, RunHealth folds budget breaches.
                        # Reuse accounting: K > 1 multiplies learn_steps/s
                        # at a fixed publish-interval-in-steps, so the WALL
                        # publish cadence — and with it the publish->adopt
                        # budget — shrinks ~K-fold; the row carries
                        # replay_ratio so a budget shift reads as the knob,
                        # not a regression.
                        ptrace.emit_lag_row(
                            step,
                            **({} if reuse_k == 1
                               else {"replay_ratio": reuse_k}),
                        )
                        # the zombie's wake-up path: claim markers are
                        # plain files, visible to a process that was
                        # paused through the whole takeover the moment it
                        # resumes.  A latched successor epoch is TERMINAL:
                        # stop training (the per-surface fences would
                        # refuse everything anyway), never checkpoint
                        # again, and fall through to the zombie return.
                        if _zombie_detected(step):
                            zombie = True
                            break
                        if monitor is not None:
                            # a preempted host stops heartbeating; the
                            # host_dead row is the external supervisor's
                            # restart/reshard signal — a hung collective
                            # would otherwise wedge this loop silently.
                            # poll() reports BOTH edges once per lease
                            # epoch: the revival side is what lets an
                            # external controller readmit the host's shard
                            # instead of treating recovery as noise.
                            dead, alive = monitor.poll()
                            for lease in dead:
                                # dead_host, not host: the envelope's `host`
                                # key is the EMITTING process index
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
                        if rplane is not None:
                            # replay-plane lifecycle: lease edges map to
                            # drop/readmit on the sampler, plus the
                            # periodic `replay_net` stats row
                            rplane.poll(step)
                    if cadence_hit(step, cfg.eval_interval, reuse_k):
                        # the drain runs on EVERY host (the cadence is a
                        # function of the lockstep step counter) so a
                        # rollback here stays lockstep; only the eval
                        # itself is main-host work
                        if not _drain():  # evaluate only verified params
                            continue
                        if is_main and spec is not None:
                            _eval_multigame(
                                cfg, spec, driver, metrics, step, games_obs)
                        elif is_main:
                            metrics.log(
                                "eval", step=step,
                                **_eval_learner(cfg, env, driver),
                            )
                    if cadence_hit(step, cfg.checkpoint_interval, reuse_k):
                        # re-check the fence at the WRITE itself: the
                        # checkpoint cadence need not share a step with the
                        # metrics cadence, and a zombie's force=True save
                        # into the successor's live Orbax dir is the one
                        # fenced surface a refusal cannot undo after the
                        # fact
                        if _zombie_detected(step):
                            zombie = True
                            break
                        if not _drain():  # checkpoint only verified params
                            continue
                        # every host calls save — Orbax treats it as a
                        # collective under jax.distributed (primary host
                        # writes, the rest join its barrier); a p0-only call
                        # would hang the pod at the next sync point.  The
                        # retry wrapper's decisions are deterministic, so
                        # hosts retry in lockstep too.
                        sup.save_checkpoint(
                            ckpt, step, host_state(driver.state),
                            # epoch in the extras: a successor's epoch-k+1
                            # checkpoint outranks the deceased epoch-k
                            # learner's in-flight save even when the
                            # zombie's step counter ran ahead
                            # (Checkpointer._steps_by_epoch); 0 is never
                            # stamped so the off path stays byte-identical
                            {"frames": frames, "weights_version": driver.weights_version,
                             **({"learner_epoch": learner_epoch}
                                if learner_epoch > 0 else {}),
                             **rng_extra(driver.key)},
                        )
                        if rplane is None:
                            sup.save_replay(cfg, memory)  # per-host shard
                        else:
                            # server-side snapshots, fenced by this step so
                            # a rewound learner can't re-trigger older ones
                            rplane.request_snapshot(step)
            if zombie:
                break  # superseded: stop acting/appending too, not just learning
        # end of run: the still-in-flight tail retires (write-back + guard)
        # before the final eval/checkpoint read the state
        _drain()
    finally:
        if prefetcher is not None:
            prefetcher.close()
        if rplane is not None:
            rplane.close()
        sup.close()
        obs_run.close(driver.step, frames)
        if heartbeat is not None:
            heartbeat.stop()
        if league_hb is not None:
            league_hb.stop()
    # last fence look before the final writes: a run that ended NORMALLY
    # while a successor was claiming (fence latched between the last cadence
    # and loop exit) must not push a final checkpoint/replay snapshot into
    # the successor's live run dir either
    if not zombie and _zombie_detected(driver.step):
        zombie = True
    if zombie:
        # A superseded incarnation stops touching the run dir HERE: no
        # final eval (its rows would read as authoritative), no final
        # checkpoint or replay snapshot (the successor's CheckpointManager
        # owns the directory now).  The terminal failover row already
        # landed; wait() only joins this process's in-flight save threads.
        ckpt.wait()
        metrics.close()
        return {
            "frames": frames,
            "learn_steps": driver.step,
            "lanes": lanes_total,
            "train_return_mean": (
                float(np.mean(returns)) if returns else float("nan")),
            "rollbacks": sup.rollbacks,
            "stalls": sup.stalls,
            "io_faults": sup.io_faults,
            "zombie_exit": True,
        }
    if is_main and spec is not None:
        final_eval = _eval_multigame(
            cfg, spec, driver, metrics, driver.step, games_obs)
    elif is_main:
        final_eval = _eval_learner(cfg, env, driver)
        metrics.log("eval", step=driver.step, **final_eval)
    else:
        final_eval = {}
    sup.save_checkpoint(
        ckpt, driver.step, host_state(driver.state),
        {"frames": frames, "weights_version": driver.weights_version,
         **({"learner_epoch": learner_epoch} if learner_epoch > 0 else {}),
         **rng_extra(driver.key)}, critical=True,
    )
    if frontier is not None:
        # the final drain may have been skipped by a rollback: catch the
        # cold-path trees up before they are persisted
        frontier.reconcile()
    if rplane is None:
        sup.save_replay(cfg, memory, critical=True)
    else:
        rplane.request_snapshot(driver.step)
    ckpt.wait()
    metrics.close()
    return {
        "frames": frames,
        "learn_steps": driver.step,
        "lanes": lanes_total,
        "train_return_mean": float(np.mean(returns)) if returns else float("nan"),
        "rollbacks": sup.rollbacks,
        "stalls": sup.stalls,
        "io_faults": sup.io_faults,
        **{f"eval_{k}": v for k, v in final_eval.items()},
    }

