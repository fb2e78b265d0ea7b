"""Driver of the fused R2D2 cells: the jitted segment `train_anakin_r2d2`
builds (`build_fused_r2d2_segment` over `DeviceSequenceReplay` and
`build_device_r2d2_learn`), carried by `init_fused_r2d2_carry`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check, ringfill, ringrows, weights
from benchmarks.drivers.fused_base import FusedDriver
from benchmarks.references import r2d2 as ref



class Driver(FusedDriver):
    def build(self):
        from rainbow_iqn_apex_tpu import train_anakin_r2d2 as prog
        from rainbow_iqn_apex_tpu.config import Config
        from rainbow_iqn_apex_tpu.envs.device_games import make_device_game
        from rainbow_iqn_apex_tpu.ops.r2d2 import (
            R2D2TrainState,
            init_r2d2_state,
        )
        from rainbow_iqn_apex_tpu.replay import device_sequence as dseq

        self.stage("the program's modules imported")
        if self.chips != 1:
            raise ValueError("this driver builds the one-chip program")
        cfg = Config(**{**self.fields, **self.traffic["fields"],
                        "seed": self.seed, "learner_devices": 1})
        self.cfg = cfg
        self.lanes, self.ticks = cfg.num_envs_per_actor, cfg.anakin_segment_ticks
        game = make_device_game(cfg.env_id.split(":", 1)[1])
        h, w = game.frame_shape
        seq_total, stride, capacity, gate = prog._seq_geometry(cfg)
        self.period, self.learns_per_tick = prog._learn_cadence(cfg)
        self.capacity = capacity
        # the seed fills all but the last `lanes` rows; the lanes' own first
        # sequences fill those, which opens the trainer's gate
        self.seeded = capacity - self.lanes
        if not self.seeded < gate <= capacity:
            raise ValueError(
                f"learn_start has to open the gate when the ring is full "
                f"({capacity} sequences; it opens at {gate}): `correct` "
                f"reads the first gradient from a dispatch of one learn step")
        self.k_fill = jax.random.fold_in(self.k_init, 2)

        shapes = weights.as_plain(jax.eval_shape(
            lambda k: init_r2d2_state(cfg, game.num_actions, k, (h, w)),
            self.k_init).params)
        self.stage("parameter shapes traced")
        replay = dseq.DeviceSequenceReplay(
            capacity=capacity, seq_len=seq_total, frame_shape=(h, w),
            lstm_size=cfg.lstm_size, lanes=self.lanes, stride=stride,
            priority_exponent=cfg.priority_exponent,
            priority_eps=cfg.priority_eps,
        )
        self.replay = replay
        self.num_actions = game.num_actions
        learn_fn = dseq.build_device_r2d2_learn(cfg, game.num_actions, replay)
        self.segment = prog.build_fused_r2d2_segment(
            cfg, game, replay, learn_fn, None)

        def make_carry(k_init, k_env):
            """Weights, optimizer state, the seeded ring and the lanes, from
            the seed, on the device in one program."""
            ts = self.seeded_train_state(R2D2TrainState, shapes, k_init)
            n = self.seeded
            k_fill = jax.random.fold_in(k_init, 2)  # self.k_fill, traced
            ss = ringfill.fill(
                replay, replay.init_state(), k_fill, n, game.num_actions)
            ss = ss._replace(
                priority=ss.priority.at[:n].set(
                    ringfill.priorities(k_fill, jnp.arange(n))),
                pos=jnp.int32(n), filled=jnp.int32(n))
            return prog.init_fused_r2d2_carry(cfg, game, ts, ss, k_env, 0)

        self.make_carry = make_carry

    def expected_steps(self, seg_from: int, seg_to: int) -> int:
        """Learn steps the cadence owes over dispatches [seg_from, seg_to),
        all after the gate opened: one per `period` ticks."""
        a, b = seg_from * self.ticks, seg_to * self.ticks
        return (b // self.period - a // self.period) * self.learns_per_tick

    # ------------------------------------------------------------- correct
    def snapshot(self):
        """Host copies of what the first learning dispatch left: the rows the
        lanes appended (the window overwrites them), priorities, params."""
        ts, ss = self.carry[0], self.carry[1]
        n, c = self.seeded, self.capacity
        self.snap = jax.tree.map(
            np.asarray, ringrows.read_rows(self.replay, ss, n, c))
        self.snap["filled"] = int(ss.filled)
        self.snapshot_state(ts, ss.priority)

    def priority0(self):
        """Before the first write-back: the seeded priorities, and 1.0
        (max-priority insertion) on the rows the lanes appended."""
        n = self.seeded
        seeded = np.asarray(
            ringfill.priorities(self.k_fill, np.arange(n)), np.float64)
        return np.concatenate([seeded, np.ones(self.snap["filled"] - n)])

    def drawn_rows(self, idx):
        """Ring rows `idx` as the reference takes them: made again from the
        seed, and a row the lanes appended from the host copy.  Both are
        logical rows by construction."""
        n, replay = self.seeded, self.replay
        made = ringfill.rows(
            self.k_fill, np.minimum(idx, n - 1), replay.L, replay.frame_shape,
            replay.lstm_size, self.num_actions)
        rows = {name: np.array(made[name]) for name in ringrows.FIELDS}
        for name, row in rows.items():
            row[idx >= n] = self.snap[name][idx[idx >= n] - n]
        return rows

    def reference_side(self, mode=None, touched=None):
        """The reference draws from the priorities above and takes the drawn
        rows from `drawn_rows`, never from the program's ring."""
        hp, snap = self.fields, self.snap

        def sample(priority, key, beta, touched):
            u01 = np.asarray(jax.random.uniform(key, (hp["batch_size"],)))
            idx, margin = ref.stratified_draw(priority, u01)
            idx = check.settle_edges(idx, margin, priority, touched)
            w = ref.is_weights(priority, idx, snap["filled"], beta)
            return ref.gather(self.drawn_rows(idx), np.arange(len(idx)), w), idx

        return check.follow(self.params0, self.target0, self.step_keys(),
                            sample, ref.loss_fn,
                            hp, self.priority0(), mode, touched)

    # ------------------------------------------------ per-layer programs
    def learn_flops(self) -> float:
        from benchmarks import flops

        return flops.r2d2_learn_flops(
            self.fields, self.replay.frame_shape, self.num_actions)

    def layer_program(self, which: str):
        """(jitted function, arguments) timing one layer on the live ring."""
        from rainbow_iqn_apex_tpu.ops.r2d2 import build_r2d2_learn_step

        ts, ss = self.carry[0], self.carry[1]
        key, beta = jax.random.PRNGKey(0), jnp.float32(0.5)
        batch, replay = self.cfg.batch_size, self.replay
        sample = jax.jit(lambda ss, k, b: replay.assemble(
            ss, replay.draw(ss, k, batch), b))
        if which == "replay_sample":
            return sample, (ss, key, beta)
        if which == "learn_only":
            drawn, _prob = sample(ss, key, beta)
            step = jax.jit(build_r2d2_learn_step(self.cfg, self.num_actions))
            return step, (ts, drawn, key)
        return None
