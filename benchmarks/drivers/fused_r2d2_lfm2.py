"""Driver of the fused R2D2 cell whose recurrent core is the LFM2 core
(`configs/cores/lfm2_8b_a1b.json`): `fused_r2d2_kanana`'s driver (whose
seeded weights read `num_experts_per_tok` and `first_expert_here`, the keys
this family has too: `weights_core.make_params` finds the four expert layers
and deals one held expert among each layer's four chosen) with the two things
that name another core replaced: the plain reference's loss, and the FLOP
count.

`reference_side` is `fused_r2d2_core.Driver.reference_side` line for line but
for the loss it differentiates, for the fifth time: the accepted drivers name
`r2d2_kimi.loss_fn`, `r2d2_kanana.loss_fn`, `r2d2_qwen3_next.loss_fn` and
`r2d2_lfm2.loss_fn` in that method's body and may not be edited here.  A
`benchmark` PR can give the base driver a `reference_loss` hook and fold the
five (PERF.md section 7; ROADMAP D14).

`dispatch` leaves the core's counters on the device: `fused_r2d2_core`'s
copies each of them to the host after every dispatch (five transfers here),
which the trainer's loop does not do, and this cell's dispatch is the shortest
of the core cells (0.90 s), so the host's time between two dispatches is its
largest share of the period (PERF.md section 6).  `counters` reads them when
a reader asks, once the window has closed, to the same numbers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check
from benchmarks.drivers import fused_r2d2_core, fused_r2d2_kanana
from benchmarks.drivers.fused_r2d2_core import REF_BLOCK, _host_gb, thin
from benchmarks.references import nets, r2d2 as ref, r2d2_lfm2


class Driver(fused_r2d2_kanana.Driver):
    def dispatch(self):
        # `FusedDriver`'s turn of the loop, past `fused_r2d2_core`'s read-back
        step, outs, k = super(fused_r2d2_core.Driver, self).dispatch()
        self._unread.append(outs[4:])
        return step, outs, k

    @property
    def counters(self):
        """{name: the core's counter in the last learn step dispatched}, as
        `fused_r2d2_core.Driver.dispatch` keeps them: the mean over a
        dispatch's learn steps, a dispatch without one changing nothing."""
        while self._unread:
            for name, value in zip(self.core.stat_names, self._unread.pop(0)):
                value = np.asarray(value)
                if np.any(np.isfinite(value)):
                    self._counters[name] = float(np.nanmean(value))
        return self._counters

    @counters.setter
    def counters(self, value):  # `build` starts them empty
        self._counters, self._unread = dict(value), []

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
        # rest (tools/calibrate.py --fault reads it; no run of a cell does).
        # The fault "online_target": with the online network where the target
        # network belongs, which shifts every target value alike; it gives
        # `loss1_rel` its upper reading (the workload file's `limits_why`)
        if mode == "online_target":
            target = params
        half, mode = mode == "half", None if mode in (
            "half", "online_target") else mode
        total = len(idx) // 2 if half else len(idx)
        block = min(REF_BLOCK, total)
        grad = jax.jit(jax.value_and_grad(
            lambda p, t, b, k: r2d2_lfm2.loss_fn(
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

    def learn_flops(self) -> float:
        from benchmarks import flops_lfm2_core

        return flops_lfm2_core.learn_flops(
            self.fields, self.core_cc, self.replay.frame_shape,
            self.num_actions)
