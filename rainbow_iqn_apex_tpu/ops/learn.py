"""The fused learner step: one XLA graph from tau sampling to Adam update.

Parity: reference `Agent.learn()` (SURVEY.md §2 row 4, §3.1/§3.4) — sample
batch -> N online-tau / N' target-tau quantile-Huber loss with double-Q action
selection, n-step targets, IS-weight multiply -> Adam step -> new priorities
from per-sample |TD|; hard target-net copy on a schedule.

TPU-first design notes (north star: BASELINE.json:5 "compile to a single XLA
graph on the learner cores"):
- `learn_step` is a pure function of (TrainState, Batch, key); jitted once per
  shape, with the TrainState donated so parameter/optimizer buffers update
  in place in HBM.
- The periodic hard target copy is folded into the same graph via a `where`
  select keyed on the step counter, so there is no second dispatch and no
  host round-trip on the update schedule.
- n-step return assembly happens host-side in the replay (ragged, pointer-y
  work); the device sees only dense [B, ...] tensors.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import chex
import jax
import jax.numpy as jnp
import optax
from flax import struct

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.models.iqn import RainbowIQN, greedy_action, q_values
from rainbow_iqn_apex_tpu.obs import device_scopes
from rainbow_iqn_apex_tpu.ops.losses import quantile_huber_loss

Params = Any


@struct.dataclass
class Batch:
    """One dense learner batch (all shapes static)."""

    obs: jnp.ndarray  # [B, H, W, C] uint8
    action: jnp.ndarray  # [B] int32
    reward: jnp.ndarray  # [B] f32 — n-step discounted return sum_k gamma^k r_k
    next_obs: jnp.ndarray  # [B, H, W, C] uint8
    discount: jnp.ndarray  # [B] f32 — gamma^n * (1 - done)
    weight: jnp.ndarray  # [B] f32 — PER importance-sampling weights
    game: Optional[jnp.ndarray] = None  # [B] int32 game ids — multi-game
    # runs only (multitask/ops.py conditions the net on it); None on the
    # single-game path, an empty pytree node that changes no numerics


@struct.dataclass
class TrainState:
    params: Params
    target_params: Params
    opt_state: optax.OptState
    step: jnp.ndarray  # [] int32 — learner steps taken


def make_optimizer(cfg: Config) -> optax.GradientTransformation:
    tx = optax.adam(cfg.learning_rate, eps=cfg.adam_eps)
    if cfg.max_grad_norm > 0:
        tx = optax.chain(optax.clip_by_global_norm(cfg.max_grad_norm), tx)
    return tx


def make_network(cfg: Config, num_actions: int, use_noise: bool = True) -> RainbowIQN:
    return RainbowIQN(
        num_actions=num_actions,
        hidden_size=cfg.hidden_size,
        num_cosines=cfg.num_cosines,
        noisy_sigma0=cfg.noisy_sigma0,
        dueling=cfg.dueling,
        use_noise=use_noise,
        compute_dtype=jnp.dtype(cfg.compute_dtype),
    )


def init_train_state(
    cfg: Config,
    num_actions: int,
    key: chex.PRNGKey,
    state_shape: Optional[Tuple[int, ...]] = None,
) -> TrainState:
    """state_shape defaults to cfg.state_shape; pass the env's actual
    (H, W, history) when the env defines its own frame size (toy envs)."""
    net = make_network(cfg, num_actions)
    k_init, k_taus, k_noise = jax.random.split(key, 3)
    dummy = jnp.zeros((1, *(state_shape or cfg.state_shape)), jnp.uint8)
    params = net.init(
        {"params": k_init, "taus": k_taus, "noise": k_noise},
        dummy,
        cfg.num_tau_samples,
    )["params"]
    opt_state = make_optimizer(cfg).init(params)
    return TrainState(
        params=params,
        target_params=jax.tree.map(jnp.copy, params),
        opt_state=opt_state,
        step=jnp.zeros((), jnp.int32),
    )


def loss_and_priorities(
    net: RainbowIQN,
    cfg: Config,
    params: Params,
    target_params: Params,
    batch: Batch,
    key: chex.PRNGKey,
    weight_scale: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Quantile-Huber loss (IS-weighted mean) + diagnostics. SURVEY §3.4.

    ``weight_scale`` ([B], optional) multiplies the IS weights — the clipped
    IMPACT reuse ratio on replay-reuse passes (``make_reuse_learn_step``).
    None (the default) leaves the trace byte-identical to the pre-reuse
    path."""
    k_sel_tau, k_sel_noise, k_tgt_tau, k_tgt_noise, k_on_tau, k_on_noise = (
        jax.random.split(key, 6)
    )

    # -- double-Q action selection: online net picks a* on s' (K acting taus).
    sel_q, _ = net.apply(
        {"params": params},
        batch.next_obs,
        cfg.num_quantile_samples,
        rngs={"taus": k_sel_tau, "noise": k_sel_noise},
    )
    a_star = greedy_action(sel_q)  # [B]

    # -- target distribution: target net on s' at a*, N' taus.
    tgt_q, _ = net.apply(
        {"params": target_params},
        batch.next_obs,
        cfg.num_tau_prime_samples,
        rngs={"taus": k_tgt_tau, "noise": k_tgt_noise},
    )  # [B, N', A]
    z_next = jnp.take_along_axis(tgt_q, a_star[:, None, None], axis=-1)[..., 0]
    td_target = jax.lax.stop_gradient(
        batch.reward[:, None] + batch.discount[:, None] * z_next
    )  # [B, N']

    # -- online distribution at the taken action, N taus.
    on_q, taus = net.apply(
        {"params": params},
        batch.obs,
        cfg.num_tau_samples,
        rngs={"taus": k_on_tau, "noise": k_on_noise},
    )  # [B, N, A]
    z_online = jnp.take_along_axis(on_q, batch.action[:, None, None], axis=-1)[..., 0]

    # A hand-written Pallas quantile-Huber kernel once sat beside this jnp
    # path.  It was never compiled on a chip, so the two were never
    # compared (not measured); the kernel was deleted.
    per_sample, td_abs = quantile_huber_loss(z_online, taus, td_target, cfg.kappa)
    weight = batch.weight
    if weight_scale is not None:
        weight = weight * weight_scale
    loss = jnp.mean(weight * per_sample)
    aux = {
        "td_abs": td_abs,
        "loss_per_sample": per_sample,
        "q_mean": on_q.mean(),
        "target_q_mean": z_next.mean(),
    }
    return loss, aux


def make_policy_logp(
    net: RainbowIQN, cfg: Config
) -> Callable[[Params, Batch, chex.PRNGKey], jnp.ndarray]:
    """[B] log-prob of each row's TAKEN action under the Boltzmann policy
    softmax(mean-of-tau q-values) — the value-based stand-in for IMPACT's
    pi(a|s) (arXiv:1912.00167) that replay-reuse importance ratios are built
    from.  Derived from the online quantile distribution at K acting taus;
    callers hand every pass the SAME key so two calls with identical params
    return bitwise-identical log-probs (ratio drift measures parameter
    drift only, never tau/noise resampling)."""

    def logp(params: Params, batch: Batch, key: chex.PRNGKey) -> jnp.ndarray:
        k_tau, k_noise = jax.random.split(key)
        quantiles, _ = net.apply(
            {"params": params},
            batch.obs,
            cfg.num_quantile_samples,
            rngs={"taus": k_tau, "noise": k_noise},
        )
        logits = jax.nn.log_softmax(q_values(quantiles), axis=-1)
        return jnp.take_along_axis(
            logits, batch.action[:, None], axis=-1)[..., 0]

    return logp


def make_reuse_learn_step(
    cfg: Config,
    pass_fn: Callable[..., Tuple[TrainState, Dict[str, jnp.ndarray]]],
    logp_fn: Callable[[Params, Batch, chex.PRNGKey], jnp.ndarray],
) -> Callable[[TrainState, Batch, chex.PRNGKey], Tuple[TrainState, Dict[str, jnp.ndarray]]]:
    """Replay-ratio > 1: one fori_loop'd K-pass learn step (IMPACT-style
    clipped reuse, arXiv:1912.00167) — XLA sees a SINGLE executable, so a
    K-fold learn rate costs one dispatch per sampled batch.

    Pass 1 is the plain learn step and snapshots the behavior policy's
    per-row log-probs (``logp_fn`` under a dedicated ratio key, shared by
    every pass).  Passes 2..K re-run the same batch with the IS weights
    scaled by clip(pi_now / pi_behavior, 1/c, c), c = ``cfg.reuse_clip`` —
    stale re-consumption of rows the policy has already moved away from is
    bounded, which is what makes K > 1 safe under staleness.  The returned
    info carries the FINAL pass's priorities (written back once per sample,
    not once per pass), the AND of every pass's finite flag (a mid-reuse
    NaN can't hide behind a later pass), and ``clip_frac`` = mean fraction
    of rows clipped per reuse pass — the K-too-high early-warning signal.
    ``state.step`` advances K per call (each pass IS an SGD step, so the
    target-copy schedule keeps its meaning)."""
    reuse_k = int(cfg.replay_ratio)
    clip_c = float(cfg.reuse_clip)

    def learn_step(
        state: TrainState, batch: Batch, key: chex.PRNGKey
    ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        k_ratio, k_loop = jax.random.split(key)
        behav_logp = jax.lax.stop_gradient(
            logp_fn(state.params, batch, k_ratio))
        # pass 1: the unscaled learn step (ratio == 1 by definition)
        state, info = pass_fn(state, batch, jax.random.fold_in(k_loop, 0))

        def body(p, carry):
            state, _info, clip_sum, finite = carry
            logp = jax.lax.stop_gradient(logp_fn(state.params, batch, k_ratio))
            ratio = jnp.exp(logp - behav_logp)
            clipped = jnp.clip(ratio, 1.0 / clip_c, clip_c)
            clip_frac = jnp.mean((ratio != clipped).astype(jnp.float32))
            state, info = pass_fn(
                state, batch, jax.random.fold_in(k_loop, p), clipped)
            return (state, info, clip_sum + clip_frac,
                    finite & info["finite"])

        state, info, clip_sum, finite = jax.lax.fori_loop(
            1, reuse_k, body,
            (state, info, jnp.zeros((), jnp.float32), info["finite"]),
        )
        info = dict(info)
        info["finite"] = finite
        info["clip_frac"] = clip_sum / max(reuse_k - 1, 1)
        # static row metadata: learn rows report reuse without a device read
        info["replay_ratio"] = reuse_k
        info["reuse_index"] = reuse_k - 1  # last completed pass this sample
        return state, info

    return learn_step


def build_learn_step(
    cfg: Config, num_actions: int
) -> Callable[[TrainState, Batch, chex.PRNGKey], Tuple[TrainState, Dict[str, jnp.ndarray]]]:
    """Returns the un-jitted learn step; callers jit/pjit it with their own
    sharding (single-chip agent vs mesh learner, parallel/apex.py).

    ``cfg.replay_ratio`` = 1 (default) returns the single-pass step,
    bitwise the PR-11 path; K > 1 wraps it in ``make_reuse_learn_step`` —
    one fori_loop'd K-pass executable with the IMPACT clip."""
    net = make_network(cfg, num_actions)
    tx = make_optimizer(cfg)

    @jax.named_scope(device_scopes.LEARN_STEP)
    def learn_step(
        state: TrainState,
        batch: Batch,
        key: chex.PRNGKey,
        weight_scale: Optional[jnp.ndarray] = None,
    ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        def loss_fn(params):
            return loss_and_priorities(
                net, cfg, params, state.target_params, batch, key,
                weight_scale)

        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
        with jax.named_scope(device_scopes.OPTIMIZER):
            updates, opt_state = tx.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)

            # Hard target copy on schedule, folded into the same XLA graph.
            step = state.step + 1
            do_copy = (step % cfg.target_update_period == 0).astype(jnp.float32)
            target_params = jax.tree.map(
                lambda t, o: do_copy * o + (1.0 - do_copy) * t,
                state.target_params,
                params,
            )

        grad_norm = optax.global_norm(grads)
        info = {
            "loss": loss,
            "priorities": aux["td_abs"],
            "q_mean": aux["q_mean"],
            "target_q_mean": aux["target_q_mean"],
            "grad_norm": grad_norm,
            # On-device NaN/Inf guard: the same loss/grad-norm finiteness
            # check TrainSupervisor.step_ok used to do with a per-step host
            # sync, folded into the XLA graph so the supervisor can defer
            # reading it to the write-back ring boundary (utils/writeback.py).
            "finite": jnp.isfinite(loss) & jnp.isfinite(grad_norm),
        }
        return (
            TrainState(
                params=params,
                target_params=target_params,
                opt_state=opt_state,
                step=step,
            ),
            info,
        )

    if cfg.replay_ratio <= 1:
        return learn_step
    return make_reuse_learn_step(cfg, learn_step, make_policy_logp(net, cfg))


def build_act_step(
    cfg: Config, num_actions: int, use_noise: bool = True
) -> Callable[[Params, jnp.ndarray, chex.PRNGKey], Tuple[jnp.ndarray, jnp.ndarray]]:
    """Batched greedy acting: (params, obs [B,H,W,C] u8, key) -> (actions [B], q [B,A]).

    Parity: reference `Agent.act` (SURVEY §3.3) — mean over K tau samples,
    argmax; noisy-net noise resampled every call via the explicit key.
    """
    net = make_network(cfg, num_actions, use_noise=use_noise)

    def act_step(params, obs, key):
        k_tau, k_noise = jax.random.split(key)
        quantiles, _ = net.apply(
            {"params": params},
            obs,
            cfg.num_quantile_samples,
            rngs={"taus": k_tau, "noise": k_noise},
        )
        return greedy_action(quantiles), q_values(quantiles)

    return act_step
