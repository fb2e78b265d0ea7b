"""Plain reference of one R2D2 learn step of the net whose recurrent core is
the LFM2 core (benchmarks/references/lfm2_core.py; the configuration is
layers 2 to 6 of LFM2-8B-A1B, 8 of each expert layer's 32 experts held): the
whole net as one pass over burn-in and trained slice from the empty state,
with the stop-gradient after the burn-in.  The heads read the core's hidden
size, not the trunk's features.

The trunk, the heads, frame stacking and the value rescaling are
benchmarks/references/r2d2.py's and nets.py's; `unroll` and `loss_fn` are
r2d2_kimi.py's, r2d2_kanana.py's, r2d2_qwen3_next.py's and r2d2_ouro.py's
line for line but for the core they call: those files name their cores at
import and may not be edited here (a `benchmark` PR can give one `loss_fn`
the core as an argument; PERF.md section 7).  Imports nothing of the program.
`hp` is the configuration file's `fields`, `cc` the core configuration file's
dict.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.references import lfm2_core as core
from benchmarks.references import nets
from benchmarks.references.r2d2 import (
    stack_frames,
    value_rescale,
    value_unrescale,
)


def unroll(params, obs, batch, noise_key, hp, cc, mode):
    """q [B, T, A] over the trained slice."""
    burn = hp["r2d2_burn_in"]
    done = batch["done"]
    prev_done = jnp.concatenate(
        [jnp.zeros_like(done[:, :1]), done[:, :-1]], axis=1)
    _kb, kt = jax.random.split(noise_key)
    b, t = obs.shape[:2]
    phi = nets.conv_trunk(
        params["ConvTrunk_0"], obs.reshape(b * t, *obs.shape[2:]), mode)
    y = core.core_forward(
        params["core"], cc, phi.reshape(b, t, -1), prev_done, burn,
        dot=lambda x, w: nets.dot(x, w, mode))[:, burn:]
    q = nets.dueling_heads(params, y.reshape(b * (t - burn), -1), kt, mode)
    return q.reshape(b, t - burn, -1)


def loss_fn(params, target_params, batch, key, hp, cc, mode=None):
    burn, n, gamma = hp["r2d2_burn_in"], hp["multi_step"], hp["gamma"]
    eta, eps_h = hp["r2d2_eta"], hp["value_rescale_eps"]
    k_on, k_tgt = jax.random.split(key)
    dt = jnp.float32
    obs = stack_frames(batch["frames"], hp["history_length"])
    q_on = unroll(params, obs, batch, k_on, hp, cc, mode)
    q_tgt = unroll(target_params, obs, batch, k_tgt, hp, cc, mode)
    a = batch["action"][:, burn:]
    r = batch["reward"][:, burn:]
    d = batch["done"][:, burn:].astype(dt)
    v = batch["valid"][:, burn:].astype(dt)
    tn = a.shape[1] - n

    q_taken = jnp.take_along_axis(q_on, a[..., None], axis=-1)[..., 0]
    a_star = jnp.argmax(jax.lax.stop_gradient(q_on), axis=-1)
    q_boot = value_unrescale(
        jnp.take_along_axis(q_tgt, a_star[..., None], axis=-1)[..., 0], eps_h)
    rn = jnp.zeros((a.shape[0], tn), dt)
    alive = jnp.ones((a.shape[0], tn), dt)
    for k in range(n):
        rn = rn + (gamma ** k) * alive * r[:, k:k + tn]
        alive = alive * (1.0 - d[:, k:k + tn])
    done_win = 1.0 - alive
    y = value_rescale(rn + (gamma ** n) * alive * q_boot[:, n:], eps_h)
    target_ok = jnp.clip(done_win + v[:, n:], 0.0, 1.0)
    mask = v[:, :tn] * target_ok
    td = (jax.lax.stop_gradient(y) - q_taken[:, :tn]) * mask
    count = jnp.maximum(mask.sum(axis=1), 1.0)
    per_seq = nets.huber(td).sum(axis=1) / count
    loss = jnp.mean(batch["weight"] * per_seq)
    abs_td = jnp.abs(td)
    prio = eta * abs_td.max(axis=1) + (1.0 - eta) * abs_td.sum(axis=1) / count
    return loss, {"priorities": prio}
