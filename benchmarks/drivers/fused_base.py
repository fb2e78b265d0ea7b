"""What fused drivers share (`fused_r2d2` today; the IQN cell of PERF.md's
Open questions brings `fused_iqn` back on it): the trainer's loop over its
jitted segment (split the key, dispatch, read back `int(ts.step)`), the
warm-up until the first learning dispatch, and the program's side of
`correct`.

A driver builds the program through the trainer's own builders in the
trainer's order (benchmarks/tests/test_same_program.py pins the result bit
for bit against `train_anakin_r2d2`), and adds
nothing to it: no eval program, no checkpoint, no metrics file.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks import keys


ADAM_B1 = 0.9  # optax.adam's default, which the trainers keep


class FusedDriver:
    """Subclasses set, in `build()`: cfg, lanes, ticks, learns_per_tick,
    segment and make_carry(k_init, k_env), and implement `snapshot()`,
    `priority0()`, `reference_side(mode, touched)` and `expected_steps()`.
    `make_state=False` builds the program without its state, for compiling
    against a described chip."""

    MAX_WARMUP_SEGMENTS = 200

    def __init__(self, fields: dict, traffic: dict, seed: int, chips: int,
                 make_state: bool = True, stage=lambda what: None):
        self.fields = dict(fields)
        self.traffic = dict(traffic)
        self.seed = int(seed)
        self.chips = int(chips)
        self.key, self.k_init, self.k_env = keys.root_keys(self.seed)
        stage("first keys made on the device")
        self.segments = 0  # dispatched so far
        self.spans = []  # (dispatch start, read-back end) of every dispatch
        self.first_learning = None
        self.stage = stage
        self.build()
        stage("segment built")
        if make_state:
            self.carry = jax.jit(self.make_carry)(self.k_init, self.k_env)
            self.params0 = jax.tree.map(np.asarray, self.carry[0].params)
            self.target0 = jax.tree.map(
                np.asarray, self.carry[0].target_params)

    # ------------------------------------------------------------ the loop
    def dispatch(self):
        """One turn of the trainer's loop.  Returns (ts.step, outs, key)."""
        self.key, k = jax.random.split(self.key)
        t0 = time.perf_counter()
        self.carry, outs = self.segment(self.carry, k)
        step = int(self.carry[0].step)  # the trainer's own sync point
        self.spans.append((t0, time.perf_counter()))
        self.segments += 1
        return step, outs, k

    @property
    def frames_per_segment(self) -> int:
        return self.ticks * self.lanes

    def warm_up(self):
        """Segments until the in-graph gate has opened and one learning
        dispatch has completed; that dispatch is kept for `correct`.  Then
        one whole learning segment, so the window starts in steady state."""
        for i in range(self.MAX_WARMUP_SEGMENTS):
            step, outs, k = self.dispatch()
            if i == 0:
                self.stage("first dispatch done (segment compiled or loaded)")
            if step > 0:
                break
        else:
            raise RuntimeError("the warm gate never opened during warm-up")
        self.first_learning = {
            "segment": self.segments - 1, "key": k, "steps": step,
            "loss": np.asarray(outs[1], np.float64),
        }
        self.stage(f"gate open after {self.segments} dispatches")
        self.snapshot()
        self.stage("first learning dispatch copied to the host")
        self.dispatch()
        self.spans.clear()

    def first_steps(self):
        """[(tick, learn index)] of the learn steps of the first learning
        dispatch, in order, from where its loss output is not NaN."""
        loss = self.first_learning["loss"]
        where = np.argwhere(np.isfinite(loss))
        return [(int(t), int(j)) for t, j in where]

    def step_keys(self):
        """[(sample key, learn key, beta)] of those steps, re-derived."""
        f = self.fields
        out = []
        for tick, j in self.first_steps():
            k_sample, k_learn = keys.learn_key(
                self.first_learning["key"], tick, self.ticks, j,
                self.learns_per_tick)
            frames = (self.first_learning["segment"] * self.ticks + tick + 1
                      ) * self.lanes
            bw = np.float32(f["priority_weight"])
            beta = np.float32(bw + (np.float32(1.0) - bw) * np.float32(
                min(frames / float(f["t_max"]), 1.0)))
            out.append((k_sample, k_learn, beta))
        return out

    def seeded_train_state(self, state_class, shapes, k_init):
        """The trainer's train state on the benchmark's seeded weights.  The
        target network is a draw of its own, as after a target update: with
        target = online the first TD errors are differences of near-equal
        numbers, and `correct` would read rounding where it should read
        arithmetic."""
        import jax.numpy as jnp
        from rainbow_iqn_apex_tpu.ops.learn import make_optimizer

        from benchmarks import weights

        sigma0 = self.cfg.noisy_sigma0
        params = weights.make_params(shapes, k_init, sigma0)
        target = weights.make_params(
            shapes, jax.random.fold_in(k_init, 1), sigma0)
        return state_class(
            params=params, target_params=target,
            opt_state=make_optimizer(self.cfg).init(params),
            step=jnp.zeros((), jnp.int32))

    def snapshot_state(self, ts, priority):
        """The part of `snapshot()` both rings share: priorities, parameters
        and Adam's first moment as the first learning dispatch left them."""
        adam = [s for s in jax.tree.leaves(
            ts.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
            if hasattr(s, "mu")]
        if len(adam) != 1:
            raise RuntimeError("expected one Adam state in the optimizer")
        self.snap["priority_after"] = np.asarray(
            priority, np.float64).reshape(-1)
        self.snap["params_after"] = jax.tree.map(np.asarray, ts.params)
        self.snap["mu"] = jax.tree.map(np.asarray, adam[0].mu)

    def program_side(self):
        """The program's side of `check.compare`.  The first gradient as Adam
        got it is read from its first moment after one step
        (mu = (1 - b1) g), so only where the first learning dispatch held
        exactly one learn step."""
        grad1 = None
        if self.first_learning["steps"] == 1:
            grad1 = jax.tree.map(
                lambda m: np.asarray(m, np.float64) / (1.0 - ADAM_B1),
                self.snap["mu"])
        loss = self.first_learning["loss"]
        return {"loss": [float(loss[t, j]) for t, j in self.first_steps()],
                "grad1": grad1,
                "priority_after": self.snap["priority_after"],
                "params_after": self.snap["params_after"]}

    def free(self):
        """Drop the program's state so the reference has the chip."""
        self.carry = None
        self.segment = None

    def peak_bytes(self):
        stats = [d.memory_stats() or {} for d in jax.local_devices()[:self.chips]]
        return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
