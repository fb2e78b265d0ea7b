"""Driver of the fused R2D2 cells whose recurrent core is not the LSTM
(`Config.core_config`; today the Kimi-Linear core): the segment, the ring,
the seeded fill and the loop are `fused_r2d2`'s, unedited.  What differs:

  * the ring stores no state (zero start state: the configuration's
    `lstm_size` 0 is the width of the ring's two stored-state columns);
  * the weights come from `weights_core` (the core's leaves) and `weights`
    (trunk and heads);
  * the reference's side of `correct` is followed here and not by
    `check.follow`, which keeps parameters, target, both moments, a gradient
    and their updated copies on the device at once: with 510M parameters
    that is over the chip's memory.  The first learn step's loss and
    gradient are summed over blocks of sequences, the clip and Adam's first
    step applied leaf by leaf; the readings are the ones `check.compare`
    takes;
  * the segment's outputs past the fourth are the core's counters.
"""

from __future__ import annotations

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check, harness, weights_core
from benchmarks.drivers import fused_r2d2
from benchmarks.drivers.fused_base import ADAM_B1
from benchmarks.references import nets, r2d2 as ref, r2d2_kimi

REF_BLOCK = 1  # sequences a block of the reference's first step
# The target network is the online one with its value head's bias this much
# higher, as after a target update in a run whose returns are rising: every
# seed's first TD errors then have the same mean, about 0.7, and the first
# gradient the same size.  With a target drawn apart (as the LSTM cell's
# driver does) the mean TD error is the difference of two nets' mean values,
# a draw round zero of its own on every seed, the gradient's size goes with
# it, and what `correct` reads against that gradient went by the seed's draw:
# 1.2e-4 to 1.0e-2 over 16 seeds, and a sound run failed (PERF.md, section 6).
TARGET_AHEAD = 1.0
# `check.compare` widens every tree it is given to float64, twice over: four
# trees of 513M numbers are 30 GiB of a 40 GiB host (more after a cold
# compile).  A leaf larger than THIN_OVER elements is therefore compared on
# every THIN_STRIDE-th of its elements, on both sides alike: norms' ratios and
# cosines are what is compared, and both keep their value on a fixed subset.
THIN_OVER, THIN_STRIDE = 2**20, 8


def thin(tree):
    def leaf(x):
        if x.size <= THIN_OVER:
            return x
        kept = x.reshape(-1)[::THIN_STRIDE]
        # a numpy slice is a view that keeps the whole leaf alive
        return kept.copy() if isinstance(x, np.ndarray) else kept

    return jax.tree.map(leaf, tree)


def _host_gb(what: str) -> None:
    """The process's resident memory on the host, to standard error: four
    trees of 513M numbers are compared on a 40 GiB machine."""
    with open("/proc/self/status") as f:
        rss = next(int(line.split()[1]) for line in f
                   if line.startswith("VmRSS:"))
    print(f"host memory: {rss / 2**20:.2f} GiB resident {what}",
          file=sys.stderr)


class Driver(fused_r2d2.Driver):
    def build(self):
        # a program from before `Config.core_config` cannot run this cell: it
        # fails here, at once, before anything is built
        from rainbow_iqn_apex_tpu.models.cores import make_core

        path = self.fields["core_config"]
        if not os.path.exists(path):
            path = os.path.join(harness.ROOT, path)
        with open(path) as f:
            self.core_cc = json.load(f)
        self.counters = {}
        super().build()
        self.core = make_core(self.cfg)
        if self.core.stored_width != self.cfg.lstm_size:
            raise ValueError(
                f"the configuration's lstm_size ({self.cfg.lstm_size}) has to "
                f"be the width of what this core stores in the ring "
                f"({self.core.stored_width})")

    def seeded_train_state(self, state_class, shapes, k_init):
        from rainbow_iqn_apex_tpu.ops.learn import make_optimizer

        make = functools.partial(
            weights_core.make_params, sigma0=self.cfg.noisy_sigma0,
            top_k=self.core_cc["num_experts_per_token"],
            first_expert=self.core_cc.get("first_expert_here", 0))
        params = make(shapes, k_init)
        ahead = params["value_out"]["b_mu"] + TARGET_AHEAD
        target = {**params,
                  "value_out": {**params["value_out"], "b_mu": ahead}}
        return state_class(
            params=params, target_params=target,
            opt_state=make_optimizer(self.cfg).init(params),
            step=jnp.zeros((), jnp.int32))

    def dispatch(self):
        step, outs, k = super().dispatch()
        for name, value in zip(self.core.stat_names, outs[4:]):
            value = np.asarray(value)
            if np.any(np.isfinite(value)):
                self.counters[name] = float(np.nanmean(value))
        return step, outs, k

    # ------------------------------------------------------------- correct
    def snapshot_state(self, ts, priority):
        super().snapshot_state(ts, priority)
        for name in ("params_after", "mu"):
            self.snap[name] = thin(self.snap[name])

    def program_side(self):
        """As `FusedDriver.program_side`, with the first gradient kept in
        float32 and Adam's first moment dropped leaf by leaf as it is read:
        a float64 copy beside it is 6 GB of a 40 GiB host."""
        if self.first_learning["steps"] != 1:
            raise ValueError("this driver reads one learn step's gradient")
        leaves, tree = jax.tree.flatten(self.snap.pop("mu"))
        for i, m in enumerate(leaves):
            leaves[i] = np.asarray(m, np.float32) / np.float32(1.0 - ADAM_B1)
        # the reference starts from the full parameters, kept on the device
        # (the program's state is freed by now); the host keeps the thinned
        # ones, which the harness hands `check.compare`
        self.params_dev = jax.tree.map(jnp.asarray, self.params0)
        self.params0 = thin(self.params0)
        loss = self.first_learning["loss"]
        return {"loss": [float(loss[t, j]) for t, j in self.first_steps()],
                "grad1": jax.tree.unflatten(tree, leaves),
                "priority_after": self.snap["priority_after"],
                "params_after": self.snap["params_after"]}

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
        # the fault "half": the reference put in the program's place with
        # the second half of the batch left out and the mean taken over the
        # rest (tools/calibrate.py --fault reads it; no run of a cell does)
        half, mode = mode == "half", None if mode == "half" else mode
        total = len(idx) // 2 if half else len(idx)
        block = min(REF_BLOCK, total)
        grad = jax.jit(jax.value_and_grad(
            lambda p, t, b, k: r2d2_kimi.loss_fn(
                p, t, b, k, hp, self.core_cc, mode), has_aux=True))
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

    # ------------------------------------------------ per-layer programs
    def learn_flops(self) -> float:
        from benchmarks import flops_kimi_core

        return flops_kimi_core.learn_flops(
            self.fields, self.core_cc, self.replay.frame_shape,
            self.num_actions)
