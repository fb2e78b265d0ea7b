"""R2D2 sequence learn step: burn-in, value rescaling, n-step double-Q.

Parity: the reference's R2D2 stretch config (BASELINE.json:10) per
Kapturowski et al. (R2D2): train a recurrent Q-net on stored-state replay
sequences — replay the first `burn_in` steps with stop-gradient to warm the
LSTM state, train on the remainder; targets use the invertible value rescale
h(x) = sign(x)(sqrt(|x|+1) - 1) + eps*x; sequence priority is the eta-mix
eta*max|td| + (1-eta)*mean|td|.

Everything is one jitted graph over [B, L] sequences: two lax.scans (burn-in
and train unroll) plus dense [B, T] target algebra — no per-step Python.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import chex
import jax
import jax.numpy as jnp
import optax
from flax import struct

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.models.cores import (
    CORE_STATS,
    make_core,
    reduce_stats,
)
from rainbow_iqn_apex_tpu.models.layers import ConvTrunk, stack_history
from rainbow_iqn_apex_tpu.models.r2d2 import R2D2Net
from rainbow_iqn_apex_tpu.obs import device_scopes
from rainbow_iqn_apex_tpu.ops.learn import make_optimizer
from rainbow_iqn_apex_tpu.ops.losses import huber

Params = Any


# ----------------------------------------------------------- value rescaling
def value_rescale(x: jnp.ndarray, eps: float = 1e-3) -> jnp.ndarray:
    """h(x) = sign(x) * (sqrt(|x| + 1) - 1) + eps * x."""
    return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + eps * x


def value_unrescale(x: jnp.ndarray, eps: float = 1e-3) -> jnp.ndarray:
    """h^-1: exact closed form (R2D2 appendix)."""
    inner = jnp.sqrt(1.0 + 4.0 * eps * (jnp.abs(x) + 1.0 + eps)) - 1.0
    return jnp.sign(x) * ((inner / (2.0 * eps)) ** 2 - 1.0)


# ------------------------------------------------------------------ batches
@struct.dataclass
class SequenceBatch:
    """[B, L] training sequences; L = burn_in + train_len."""

    obs: jnp.ndarray  # [B, L, H, W, C] uint8
    action: jnp.ndarray  # [B, L] int32
    reward: jnp.ndarray  # [B, L] f32
    done: jnp.ndarray  # [B, L] bool — episode ended AT step t
    valid: jnp.ndarray  # [B, L] bool — step belongs to the episode
    init_c: jnp.ndarray  # [B, lstm] stored recurrent state at sequence start
    init_h: jnp.ndarray  # [B, lstm]
    weight: jnp.ndarray  # [B] f32 IS weights


def stack_seq_frames(obs_seq: jnp.ndarray, history: int) -> jnp.ndarray:
    """The definition of what a learn step sees of a stored sequence:
    [B, L, H, W, 1] -> [B, L, H, W, history], channel k holding the frame
    from t-(history-1-k).

    The R2D2 paper feeds 4-stacked frames AND an LSTM; sequences are stored
    as single frames (dedup).  Steps earlier than the sequence start are
    zero, which only touches the first history-1 steps of the burn-in region
    (burn_in >= history-1, `build_r2d2_learn_step` insists), whose sole job
    is LSTM warm-up.  The learn step does not make this array: the trunk's
    first conv reads the same history from the single frames
    (`layers.StemConv`), and tests hold it to this function.
    """
    if history <= 1:
        return obs_seq
    return stack_history(obs_seq, jnp.zeros_like(obs_seq[:, : history - 1]))


def stem_from_frames_share(
    cfg: Config, frame_shape: Tuple[int, int], learner_chips: int = 1
) -> float:
    """For the trainers' `learn` rows: 1.0 where the learn step, handed a
    ring's single frames of `frame_shape` with its batch on `learner_chips`
    chips, compiles the first conv's reading from the frames
    (`layers.StemConv`); 0.0 where it runs the plain conv on stacks (history
    1, a frame the conv's stride does not divide, a batch split over a
    mesh).  A fact of the trace: the host writes it, the step puts nothing
    out for it."""
    return float(cfg.history_length > 1 and ConvTrunk.stem_reads_frames(
        *frame_shape, chips=learner_chips))


def to_device_seq_batch(s) -> "SequenceBatch":
    """Host SequenceSample -> device SequenceBatch (async jnp.asarray)."""
    return SequenceBatch(
        obs=jnp.asarray(s.obs),
        action=jnp.asarray(s.action),
        reward=jnp.asarray(s.reward),
        done=jnp.asarray(s.done),
        valid=jnp.asarray(s.valid),
        init_c=jnp.asarray(s.init_c),
        init_h=jnp.asarray(s.init_h),
        weight=jnp.asarray(s.weight),
    )


@struct.dataclass
class R2D2TrainState:
    params: Params
    target_params: Params
    opt_state: optax.OptState
    step: jnp.ndarray


def make_r2d2_network(cfg: Config, num_actions: int, use_noise: bool = True) -> R2D2Net:
    return R2D2Net(
        num_actions=num_actions,
        lstm_size=cfg.lstm_size,
        hidden_size=cfg.hidden_size,
        noisy_sigma0=cfg.noisy_sigma0,
        dueling=cfg.dueling,
        use_noise=use_noise,
        compute_dtype=jnp.dtype(cfg.compute_dtype),
        core=make_core(cfg),
    )


def init_r2d2_state(
    cfg: Config,
    num_actions: int,
    key: chex.PRNGKey,
    frame_shape: Tuple[int, int],
    channels: Optional[int] = None,
) -> R2D2TrainState:
    """channels defaults to cfg.history_length (frame-stacked input)."""
    net = make_r2d2_network(cfg, num_actions)
    k1, k2 = jax.random.split(key)
    dummy = jnp.zeros((1, 2, *frame_shape, channels or cfg.history_length), jnp.uint8)
    params = net.init(
        {"params": k1, "noise": k2}, dummy, net.initial_state(1)
    )["params"]
    opt_state = make_optimizer(cfg).init(params)
    return R2D2TrainState(
        params=params,
        target_params=jax.tree.map(jnp.copy, params),
        opt_state=opt_state,
        step=jnp.zeros((), jnp.int32),
    )


def _unroll(
    net: R2D2Net,
    params: Params,
    batch: SequenceBatch,
    burn_in: int,
    noise_key: chex.PRNGKey,
    history: int = 1,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Burn-in (stop-grad) then train unroll; returns q [B, T, A] for the
    train slice and the counters the core sowed over it.  The core's state
    resets where a step follows a terminal.  Where `batch.obs` holds single
    frames of a `history` > 1 (stacked input passes through as it is), each
    pass is handed the history-1 frames before its first step: zeros, then
    the burn-in's last ones."""
    before_burn = before_train = None
    if history > 1 and batch.obs.shape[-1] == 1:
        before_train = batch.obs[:, burn_in - (history - 1):burn_in]
        before_burn = jnp.zeros_like(before_train)
    # reset BEFORE step t when the previous step ended the episode
    prev_done = jnp.concatenate(
        [jnp.zeros_like(batch.done[:, :1]), batch.done[:, :-1]], axis=1
    )
    state = net.the_core().from_stored(batch.init_c, batch.init_h)
    kb, kt = jax.random.split(noise_key)
    if burn_in > 0:
        _, state = net.apply(
            {"params": params},
            batch.obs[:, :burn_in],
            state,
            resets=prev_done[:, :burn_in],
            frames_before=before_burn,
            rngs={"noise": kb},
        )
        state = jax.lax.stop_gradient(state)
    (q, _), sown = net.apply(
        {"params": params},
        batch.obs[:, burn_in:],
        state,
        resets=prev_done[:, burn_in:],
        frames_before=before_train,
        rngs={"noise": kt},
        mutable=[CORE_STATS],
    )
    return q, reduce_stats(sown)  # [B, T, A]


def build_r2d2_learn_step(
    cfg: Config, num_actions: int
) -> Callable[[R2D2TrainState, SequenceBatch, chex.PRNGKey],
              Tuple[R2D2TrainState, Dict[str, jnp.ndarray]]]:
    net = make_r2d2_network(cfg, num_actions)
    tx = make_optimizer(cfg)
    burn, n, gamma = cfg.r2d2_burn_in, cfg.multi_step, cfg.gamma
    eta, eps_h = cfg.r2d2_eta, cfg.value_rescale_eps

    history = cfg.history_length
    if history > 1 and burn < history - 1:
        raise ValueError(
            f"r2d2_burn_in ({burn}) must be >= history_length-1 "
            f"({history - 1}): on-device frame stacking zero-pads the first "
            "history-1 steps of each sequence, which must fall inside the "
            "burn-in region or the loss trains on observations the actor "
            "never saw"
        )

    @jax.named_scope(device_scopes.LEARN_STEP)
    def learn_step(state: R2D2TrainState, batch: SequenceBatch, key: chex.PRNGKey):
        k_on, k_tgt = jax.random.split(key)
        T = batch.obs.shape[1] - burn  # train slice length

        def loss_fn(params):
            q_on, core_stats = _unroll(
                net, params, batch, burn, k_on, history)  # [B, T, A]
            # Double-Q selection reuses the online unroll (stop-grad) rather
            # than paying a third full conv+LSTM unroll for an independent
            # noise draw — selection and evaluation already use different
            # nets, which is where double-Q's bias correction comes from.
            q_sel = jax.lax.stop_gradient(q_on)
            q_tgt, _ = _unroll(
                net, state.target_params, batch, burn, k_tgt, history)

            a = batch.action[:, burn:]  # [B, T]
            r = batch.reward[:, burn:]
            d = batch.done[:, burn:].astype(jnp.float32)
            v = batch.valid[:, burn:].astype(jnp.float32)

            q_taken = jnp.take_along_axis(q_on, a[..., None], axis=-1)[..., 0]

            # --- n-step double-Q bootstrap, all within the train slice ------
            a_star = jnp.argmax(q_sel, axis=-1)  # [B, T]
            q_boot = value_unrescale(
                jnp.take_along_axis(q_tgt, a_star[..., None], axis=-1)[..., 0],
                eps_h,
            )
            # shifted windows: for t in [0, T-n): R = sum_k gamma^k r[t+k]
            # (truncated at terminal), bootstrap from t+n if alive.
            Tn = T - n
            gammas = gamma ** jnp.arange(n, dtype=jnp.float32)
            r_win = jnp.stack([r[:, k : k + Tn] for k in range(n)], axis=-1)  # [B,Tn,n]
            d_win = jnp.stack([d[:, k : k + Tn] for k in range(n)], axis=-1)
            alive_prefix = jnp.cumprod(1.0 - d_win[..., :-1], axis=-1)
            alive_prefix = jnp.concatenate(
                [jnp.ones_like(alive_prefix[..., :1]), alive_prefix], axis=-1
            )
            rn = (r_win * alive_prefix * gammas).sum(axis=-1)  # [B, Tn]
            done_win = jnp.clip(d_win.sum(axis=-1), 0.0, 1.0)
            no_done = 1.0 - done_win
            y = value_rescale(
                rn + (gamma**n) * no_done * q_boot[:, n:], eps_h
            )
            td = jax.lax.stop_gradient(y) - q_taken[:, :Tn]
            # A step's target is usable iff its n-step window ends inside the
            # episode: either a true terminal falls within the window (reward
            # sum truncates there, no bootstrap) or the bootstrap step t+n is
            # itself valid. A time-limit TRUNCATION ends the valid region
            # with done=False (two-channel cuts, replay/sequence.py), so
            # windows that cross it have neither — they are masked out rather
            # than bootstrapping from padding (which would teach V=0 at the
            # cut, the time-limit bias the frame replay also avoids).
            target_ok = jnp.clip(done_win + v[:, n:], 0.0, 1.0)
            mask = v[:, :Tn] * target_ok
            td = td * mask

            per_seq_loss = (huber(td, 1.0).sum(axis=1)) / jnp.maximum(
                mask.sum(axis=1), 1.0
            )
            loss = jnp.mean(batch.weight * per_seq_loss)

            abs_td = jnp.abs(td)
            max_td = abs_td.max(axis=1)
            mean_td = abs_td.sum(axis=1) / jnp.maximum(mask.sum(axis=1), 1.0)
            priorities = eta * max_td + (1.0 - eta) * mean_td
            aux = {
                "priorities": priorities,
                "q_mean": (q_taken * v).sum() / jnp.maximum(v.sum(), 1.0),
                "core_stats": core_stats,
            }
            return loss, aux

        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
        with jax.named_scope(device_scopes.OPTIMIZER):
            updates, opt_state = tx.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
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
            "priorities": aux["priorities"],
            "q_mean": aux["q_mean"],
            "grad_norm": grad_norm,
            # on-device NaN/Inf guard flag (same contract as ops/learn.py:
            # checked host-side at the write-back ring boundary)
            "finite": jnp.isfinite(loss) & jnp.isfinite(grad_norm),
            **aux["core_stats"],
        }
        return (
            R2D2TrainState(
                params=params,
                target_params=target_params,
                opt_state=opt_state,
                step=step,
            ),
            info,
        )

    return learn_step


def as_actor_input(obs, history: int):
    """Normalise actor observations to [B, H, W, C] and enforce that C
    matches the training channel count (the host FrameStacker supplies the
    stack when history > 1).  Stays host NumPy — the caller decides how the
    array reaches the device (jit argument upload, or
    make_array_from_process_local_data on a multi-host mesh) so no extra
    host->device->host round trip sneaks into the actor tick."""
    import numpy as np

    x = np.asarray(obs)
    if x.ndim == 3:
        x = x[..., None]
    if x.shape[-1] != history:
        raise ValueError(
            f"actor obs has {x.shape[-1]} channels but history_length is "
            f"{history}; feed FrameStacker output (or raw [B,H,W] frames "
            "when history_length == 1)"
        )
    return x


def build_r2d2_act_step(
    cfg: Config, num_actions: int, use_noise: bool = True,
    with_stats: bool = False,
) -> Callable:
    """Recurrent acting: (params, obs [B,H,W,C] u8, state, key) ->
    (action [B], q [B,A], new_state), and with `with_stats` a fourth entry,
    the counters the core sowed over the step ({name: scalar}).  C must match
    the training channels (cfg.history_length when frame-stacking; the host
    FrameStacker supplies it on the actor side)."""
    net = make_r2d2_network(cfg, num_actions, use_noise=use_noise)

    def act_step(params, obs, state, key):
        (q, new_state), sown = net.apply(
            {"params": params},
            obs[:, None],  # [B, 1, H, W, C]
            state,
            rngs={"noise": key},
            mutable=[CORE_STATS] if with_stats else [],
        )
        q = q[:, 0]
        out = (jnp.argmax(q, axis=-1).astype(jnp.int32), q, new_state)
        return out + (reduce_stats(sown),) if with_stats else out

    return act_step
