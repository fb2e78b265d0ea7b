"""Plain reference of one R2D2 learn step of the net whose recurrent core is
the Laguna core (benchmarks/references/laguna_core.py; the configuration is
layers 0 to 4 of Laguna-XS.2, 16 of each expert layer's 256 experts held): the
whole net as one pass over burn-in and trained slice from the empty state,
with the stop-gradient after the burn-in, the full layers over the whole
sequence and the sliding layers over the last `sliding_window` steps.  The
heads read the core's hidden size, not the trunk's features.

The trunk, the heads, frame stacking and the value rescaling are
benchmarks/references/r2d2.py's and nets.py's; `loss_fn` is r2d2_lfm2.py's
(and the four before it) line for line but for the core it calls: those files
name their cores at import and may not be edited here (a `benchmark` PR can
give one `loss_fn` the core as an argument; PERF.md section 7).  `unroll`
differs in two things this cell's size forces: the trunk runs over the
frames a sequence at a time (8 x 1,024 stacked frames at once are 0.8 GB of
float32 input before the first convolution's output), and `mode`
"ignore_span" runs the sliding layers as full ones, the control a comparison
that sees the band has to fail.  Imports nothing of the program.  `hp` is the
configuration file's `fields`, `cc` the core configuration file's dict.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.references import laguna_core as core
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
    ignore_span, mode = mode == "ignore_span", (
        None if mode == "ignore_span" else mode)
    phi = jax.lax.map(
        lambda o: nets.conv_trunk(params["ConvTrunk_0"], o, mode), obs)
    y = core.core_forward(
        params["core"], cc, phi.reshape(b, t, -1), prev_done, burn,
        dot=lambda x, w: nets.dot(x, w, mode),
        ignore_span=ignore_span)[:, burn:]
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
