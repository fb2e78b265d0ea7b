"""Driver of the fused R2D2 cell whose recurrent core is the Laguna core
(`configs/cores/laguna_xs_2.json`): `fused_r2d2_lfm2`'s driver (the core's
counters left on the device until a reader asks; seeded weights by the keys
this family has too, `num_experts_per_tok` and `first_expert_here`) with what
names another core or another traffic replaced:

  * `build`: the traffic truncates an episode at `device_game_tick_cap` ticks,
    which the game is told where it is made.  `fused_r2d2.Driver.build` makes
    it from the env id alone (`make_device_game(name)`) and may not be edited
    here, so its body stands here again with the one call changed, behind
    `fused_r2d2_core.Driver.build`'s reading of the core's file.  A
    `benchmark` PR can give the base driver the game's factory as a method
    and fold the two (PERF.md section 7).  A program from before this
    family fails at the core's file, at once, and one from before
    `Config.device_game_tick_cap` at `Config(**fields)`.
  * `reference_side`: `fused_r2d2_lfm2.Driver.reference_side` line for line
    but for the loss it differentiates and one more mode, for the sixth time
    (that method names `r2d2_lfm2.loss_fn` in its body).  The mode
    "ignore_span" is this architecture's own control: the reference with its
    sliding layers run as full attention, put in the program's place; a
    comparison that passes it does not see the band.
  * `learn_flops`: benchmarks/flops_laguna_core.py.
"""

from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check, harness, ringfill, weights
from benchmarks.drivers import fused_r2d2_lfm2
from benchmarks.drivers.fused_r2d2_core import REF_BLOCK, _host_gb, thin
from benchmarks.references import nets, r2d2 as ref, r2d2_laguna

# modes of `reference_side` that are faults of the reference's own and not an
# arithmetic of `nets.MODES`
FAULTS = ("half", "online_target", "ignore_span")


class Driver(fused_r2d2_lfm2.Driver):
    def build(self):
        from rainbow_iqn_apex_tpu import train_anakin_r2d2 as prog
        from rainbow_iqn_apex_tpu.config import Config
        from rainbow_iqn_apex_tpu.envs.device_games import make_device_game
        from rainbow_iqn_apex_tpu.models.cores import make_core
        from rainbow_iqn_apex_tpu.ops.r2d2 import (
            R2D2TrainState,
            init_r2d2_state,
        )
        from rainbow_iqn_apex_tpu.replay import device_sequence as dseq

        path = self.fields["core_config"]
        if not os.path.exists(path):
            path = os.path.join(harness.ROOT, path)
        with open(path) as f:
            self.core_cc = json.load(f)
        self.counters = {}
        self.stage("the program's modules imported")
        if self.chips != 1:
            raise ValueError("this driver builds the one-chip program")
        cfg = Config(**{**self.fields, **self.traffic["fields"],
                        "seed": self.seed, "learner_devices": 1})
        self.cfg = cfg
        self.lanes, self.ticks = cfg.num_envs_per_actor, cfg.anakin_segment_ticks
        game = make_device_game(
            cfg.env_id.split(":", 1)[1], cfg.device_game_tick_cap)
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
        self.core = make_core(cfg)
        if self.core.stored_width != cfg.lstm_size:
            raise ValueError(
                f"the configuration's lstm_size ({cfg.lstm_size}) has to "
                f"be the width of what this core stores in the ring "
                f"({self.core.stored_width})")

    def reference_side(self, mode=None, touched=None):
        hp, snap = self.fields, self.snap
        steps = self.step_keys()
        if len(steps) != 1:
            raise ValueError(
                f"the first learning dispatch held {len(steps)} learn steps; "
                f"this driver follows exactly one")
        k_sample, k_learn, beta = steps[0]
        priority = self.priority0()
        u01 = np.asarray(jax.random.uniform(k_sample, (hp["batch_size"],)))
        idx, margin = ref.stratified_draw(priority, u01)
        idx = check.settle_edges(idx, margin, priority, touched)
        weight = ref.is_weights(priority, idx, snap["filled"], beta)
        batch = ref.gather(self.drawn_rows(idx), np.arange(len(idx)), weight)

        _host_gb("as the reference starts")
        if self.target0 is not None:  # the host's copy is needed no longer
            self.target_dev = jax.tree.map(jnp.asarray, self.target0)
            self.target0 = None
        params, target = self.params_dev, self.target_dev
        # the faults, each the reference put in the program's place with one
        # thing wrong: "half" with the second half of the batch left out and
        # the mean taken over the rest; "online_target" with the online
        # network where the target network belongs (`loss1_rel`'s upper
        # reading); "ignore_span" with the sliding layers attending over the
        # whole sequence (the workload file's `limits_why`)
        fault, mode = (mode, None) if mode in FAULTS else (None, mode)
        if fault == "online_target":
            target = params
        loss_mode = "ignore_span" if fault == "ignore_span" else mode
        total = len(idx) // 2 if fault == "half" else len(idx)
        block = min(REF_BLOCK, total)
        grad = jax.jit(jax.value_and_grad(
            lambda p, t, b, k: r2d2_laguna.loss_fn(
                p, t, b, k, hp, self.core_cc, loss_mode), has_aux=True))
        add = jax.jit(lambda acc, g, s: jax.tree.map(
            lambda a, x: a + s * x, acc, g), donate_argnums=(0,))
        acc = jax.tree.map(jnp.zeros_like, params)
        loss, prio = 0.0, []
        for lo in range(0, total, block):
            part = {k: v[lo:lo + block] for k, v in batch.items()}
            share = part["weight"].shape[0] / total
            (part_loss, aux), g = grad(params, target, part, k_learn)
            acc = add(acc, g, jnp.float32(share))
            loss += share * float(part_loss)
            prio.append(np.asarray(aux["priorities"], np.float64))
        del g, target
        gn, clip = float(nets.global_norm(acc)), hp["max_grad_norm"]
        scale = 1.0 if not clip > 0 or gn < clip else clip / gn
        adam = jax.jit(functools.partial(
            nets.adam_step, lr=hp["learning_rate"], eps=hp["adam_eps"],
            clip=0.0, t=1))

        def first_step(p, g):  # Adam's first step of one leaf, moments zero
            z = jnp.zeros_like(p)
            return np.asarray(thin(adam(p, g * scale, z, z)[0]))

        out = {"loss": [loss], "priority0": priority.copy(), "idx1": idx,
               "params_after": jax.tree.map(first_step, params, acc),
               "grad1": jax.tree.map(
                   lambda g: np.asarray(thin(g * scale), np.float32), acc)}
        written = (np.concatenate(prio) + hp["priority_eps"]) ** hp[
            "priority_exponent"]
        seen = idx[:total]
        priority[seen] = np.where(priority[seen] > 0, written, 0.0)
        out["priority_after"] = priority
        _host_gb("as the reference ends")
        return out

    def learn_flops(self) -> float:
        from benchmarks import flops_laguna_core

        return flops_laguna_core.learn_flops(
            self.fields, self.core_cc, self.replay.frame_shape,
            self.num_actions)
