"""The Agent: acting, learning, target sync, checkpoint save/load.

Parity: reference `rainbowiqn/agent.py` `Agent` (SURVEY.md §2 row 4, §3.3) —
`act(state)` (greedy over the mean of K tau samples, noisy-net exploration),
`learn(memory)` (quantile-Huber + Adam + priority write-back), scheduled
target-net update, save/load.

TPU-first notes: the Agent is a thin host-side facade over two pure jitted
functions (act_step, learn_step).  All mutable state lives in one TrainState
pytree in device memory (donated through the learn step) and an explicit PRNG
key; nothing else to get wrong under jit.  The per-lane frame-stack rolling
state is host NumPy — it belongs to the env/actor side of the host-device
seam, so frames cross to HBM exactly once per tick as one [L, H, W, hist]
uint8 tensor.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import chex
import jax
import jax.numpy as jnp
import numpy as np

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.ops.learn import (
    Batch,
    TrainState,
    build_act_step,
    build_learn_step,
    init_train_state,
)
from rainbow_iqn_apex_tpu.replay.buffer import SampledBatch
from rainbow_iqn_apex_tpu.utils import hostsync


def put_frames(x: np.ndarray) -> jnp.ndarray:
    """Transfer uint8 frame tensors as a flat byte stream, reshape on device.

    Rank>=3 uint8 transfers can pay a per-array host/transport (re)tiling
    cost on some PJRT transports.  Whether they do on a directly attached
    chip is not measured (ROADMAP S2).  The flat view is zero-copy on the
    host and the device-side reshape is layout bookkeeping.
    """
    arr = np.ascontiguousarray(x)
    return jnp.asarray(arr.reshape(-1)).reshape(arr.shape)


def to_device_batch(sample: SampledBatch) -> Batch:
    """Host SampledBatch -> device Batch (async transfers via jnp.asarray)."""
    game = getattr(sample, "game", None)
    return Batch(
        obs=put_frames(sample.obs),
        action=jnp.asarray(sample.action),
        reward=jnp.asarray(sample.reward),
        next_obs=put_frames(sample.next_obs),
        discount=jnp.asarray(sample.discount),
        weight=jnp.asarray(sample.weight),
        game=None if game is None else jnp.asarray(game, jnp.int32),
    )


class FrameStacker:
    """Rolling [L, H, W, hist] uint8 stack with per-lane terminal reset."""

    def __init__(self, lanes: int, frame_shape: Tuple[int, int], history: int):
        self.buf = np.zeros((lanes, *frame_shape, history), np.uint8)

    def push(self, frames: np.ndarray) -> np.ndarray:
        """Shift in the newest frame; returns the stacked state (a view copy)."""
        self.buf[..., :-1] = self.buf[..., 1:]
        self.buf[..., -1] = frames
        return self.buf.copy()

    def reset_lanes(self, mask: np.ndarray) -> None:
        """Zero the history of lanes whose episode just ended (reference
        zero-stack reset semantics)."""
        self.buf[mask] = 0


class Agent:
    def __init__(
        self,
        cfg: Config,
        num_actions: int,
        key: chex.PRNGKey,
        train: bool = True,
        state_shape: Optional[Tuple[int, ...]] = None,
    ):
        self.cfg = cfg
        self.num_actions = num_actions
        key, init_key = jax.random.split(key)
        self.key = key
        # replay reuse (cfg.replay_ratio = K > 1): one learn_batch dispatch
        # is a fused K-pass executable, so state.step — and the host mirror
        # — advance K per call (ops/learn.py make_reuse_learn_step)
        self.reuse_k = max(int(cfg.replay_ratio), 1)
        self._host_step: Optional[int] = None  # host mirror of state.step
        self.state: TrainState = init_train_state(
            cfg, num_actions, init_key, state_shape=state_shape
        )
        self._act = jax.jit(build_act_step(cfg, num_actions, use_noise=True))
        self._act_eval = jax.jit(
            build_act_step(cfg, num_actions, use_noise=cfg.eval_noisy)
        )
        self._learn = (
            jax.jit(build_learn_step(cfg, num_actions), donate_argnums=0)
            if train
            else None
        )

    # ------------------------------------------------------------------ acting
    def _next_key(self) -> chex.PRNGKey:
        self.key, k = jax.random.split(self.key)
        return k

    def act(self, stacked_obs: np.ndarray, eval_mode: bool = False) -> np.ndarray:
        """Greedy actions for a [L, H, W, hist] uint8 batch.  Noisy-net noise
        is resampled every call (reference per-step resample, SURVEY §3.2)."""
        fn = self._act_eval if eval_mode else self._act
        actions, _ = fn(self.state.params, put_frames(stacked_obs), self._next_key())
        # the actor->env hand-off is an OBLIGATORY host materialization (the
        # env lives on host) — same sanctioned sync as ApexDriver.act
        with hostsync.sanctioned():
            return np.asarray(actions)

    # ---------------------------------------------------------------- learning
    def learn(self, sample: SampledBatch) -> Dict[str, Any]:
        """One learner step on a host SampledBatch; returns info with host
        priorities for the replay write-back."""
        return self.learn_batch(to_device_batch(sample))

    def learn_batch(self, batch: Batch) -> Dict[str, Any]:
        """One learner step on an already-staged device Batch (prefetch
        path).  Dispatch-only: ``info`` values stay device arrays (JAX async
        dispatch) so the caller decides when — if ever per step — to sync."""
        self._state, info = self._learn(self._state, batch, self._next_key())
        if self._host_step is not None:
            self._host_step += self.reuse_k
        return info

    # `state` invalidates the host step mirror on direct assignment (resume,
    # tests); learn_batch bypasses the setter and increments the mirror, so
    # reading `step` in the hot loop never blocks on the device queue.
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

    # ---------------------------------------------------------------- rollback
    def load_snapshot(self, state, key) -> None:
        """NaN-guard rollback target (parallel/supervisor.py): replace the
        live TrainState + PRNG key with the supervisor's last-good host
        copy.  The poisoned donated buffers are simply dropped."""
        self.state = jax.tree.map(jnp.asarray, state)
        self.key = jnp.asarray(key)

    # ------------------------------------------------------- league adoption
    def adopt_params(self, host_params) -> None:
        """League exploit adoption (league/member.py, docs/LEAGUE.md):
        replace online AND target params with a copied member's weights —
        called only at a drained boundary.  Optimizer moments are re-init
        fresh: Adam statistics accumulated around the LOSER's trajectory
        are meaningless at the winner's point in weight space, and a
        deterministic re-init is reproducible where stale moments are not.
        The step counter and PRNG stream are untouched (cadences and
        exploration continue where the member left off)."""
        from rainbow_iqn_apex_tpu.league.member import graft_tree
        from rainbow_iqn_apex_tpu.ops.learn import make_optimizer

        params = jax.tree.map(
            jnp.asarray, graft_tree(self._state.params, host_params))
        self.state = self._state.replace(
            params=params,
            target_params=jax.tree.map(jnp.copy, params),
            opt_state=make_optimizer(self.cfg).init(params),
        )

    def retune(self, learning_rate: Optional[float] = None) -> None:
        """Mid-run live-gene adoption: rebuild the jitted learn step under
        the new hyperparameters (one recompile per exploit event — rare by
        construction).  Replay-side genes (n_step, priority_exponent) are
        retuned on the replay object by the loop; this covers the genes
        baked into the learn executable."""
        if learning_rate is None or self._learn is None:
            return
        self.cfg = self.cfg.replace(learning_rate=float(learning_rate))
        self._learn = jax.jit(
            build_learn_step(self.cfg, self.num_actions), donate_argnums=0
        )

    # ------------------------------------------------------------- weight sync
    def params_for_publish(self):
        """Online params as the learner publishes them to actors (the Redis
        weight-mailbox equivalent; bf16-cast when configured to halve sync
        bytes — SURVEY §5 'weight mailbox')."""
        if self.cfg.bf16_weight_sync:
            return jax.tree.map(lambda p: p.astype(jnp.bfloat16), self.state.params)
        return self.state.params

    def load_published(self, params) -> None:
        self.state = self.state.replace(
            params=jax.tree.map(lambda p: p.astype(jnp.float32), params)
        )
