"""Plain reference of one R2D2 learn step over a stored-state sequence batch
(Kapturowski et al. 2019): frame stacking inside the sequence, conv trunk,
LSTM unroll with burn-in from the stored state, dueling noisy heads, n-step
double-Q targets under value rescaling, masked Huber loss, the eta-mixed
sequence priority, Adam.  Also the sequence ring's draw: stratified
proportional sampling over the stored priorities with IS weights.

Imports nothing of the program.  `hp` is the configuration file's `fields`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references import nets


def value_rescale(x, eps):
    return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + eps * x


def value_unrescale(x, eps):
    inner = jnp.sqrt(1.0 + 4.0 * eps * (jnp.abs(x) + 1.0 + eps)) - 1.0
    return jnp.sign(x) * ((inner / (2.0 * eps)) ** 2 - 1.0)


def stack_frames(frames, history):
    """[B, L, H, W] uint8 -> [B, L, H, W, history] float in [0,1]; channel k
    is the frame of step t-(history-1-k), zero before the sequence starts."""
    x = frames.astype(jnp.float32) / 255.0
    chans = [
        jnp.pad(x[:, : x.shape[1] - k], ((0, 0), (k, 0), (0, 0), (0, 0)))
        for k in range(history - 1, -1, -1)
    ]
    return jnp.stack(chans, axis=-1)


def lstm_unroll(cell, feats, resets, state, mode):
    """feats [B, T, F], resets [B, T] (zero the state BEFORE step t)."""

    def gate(g, x, h):
        return (nets.dot(x, cell[f"i{g}"]["kernel"], mode)
                + nets.dot(h, cell[f"h{g}"]["kernel"], mode)
                + cell[f"h{g}"]["bias"])

    def step(carry, xs):
        c, h = carry
        x, r = xs
        keep = (1.0 - r.astype(jnp.float32))[:, None]
        c, h = c * keep, h * keep
        i = jax.nn.sigmoid(gate("i", x, h))
        f = jax.nn.sigmoid(gate("f", x, h))
        g = jnp.tanh(gate("g", x, h))
        o = jax.nn.sigmoid(gate("o", x, h))
        c = f * c + i * g
        h = o * jnp.tanh(c)
        return (c, h), h

    state, outs = jax.lax.scan(
        step, state, (jnp.moveaxis(feats, 1, 0), jnp.moveaxis(resets, 1, 0)))
    return jnp.moveaxis(outs, 0, 1), state


def unroll(params, obs, batch, noise_key, hp, mode):
    """q [B, T, A] over the trained slice, after a stop-gradient burn-in."""
    burn = hp["r2d2_burn_in"]
    done = batch["done"]
    prev_done = jnp.concatenate(
        [jnp.zeros_like(done[:, :1]), done[:, :-1]], axis=1)
    state = (batch["init_c"], batch["init_h"])
    _kb, kt = jax.random.split(noise_key)
    cell = params["lstm"]["cell"]

    def features(x):
        b, t = x.shape[:2]
        phi = nets.conv_trunk(
            params["ConvTrunk_0"], x.reshape(b * t, *x.shape[2:]), mode)
        return phi.reshape(b, t, -1)

    if burn > 0:
        _, state = lstm_unroll(
            cell, features(obs[:, :burn]), prev_done[:, :burn], state, mode)
        state = jax.lax.stop_gradient(state)
    outs, _ = lstm_unroll(
        cell, features(obs[:, burn:]), prev_done[:, burn:], state, mode)
    b, t, m = outs.shape
    q = nets.dueling_heads(params, outs.reshape(b * t, m), kt, mode)
    return q.reshape(b, t, -1)


def loss_fn(params, target_params, batch, key, hp, mode=None):
    burn, n, gamma = hp["r2d2_burn_in"], hp["multi_step"], hp["gamma"]
    eta, eps_h = hp["r2d2_eta"], hp["value_rescale_eps"]
    k_on, k_tgt = jax.random.split(key)
    dt = jnp.float32
    obs = stack_frames(batch["frames"], hp["history_length"])
    q_on = unroll(params, obs, batch, k_on, hp, mode)
    q_tgt = unroll(target_params, obs, batch, k_tgt, hp, mode)
    a = batch["action"][:, burn:]
    r = batch["reward"][:, burn:]
    d = batch["done"][:, burn:].astype(dt)
    v = batch["valid"][:, burn:].astype(dt)
    t_len = a.shape[1]
    tn = t_len - n

    q_taken = jnp.take_along_axis(q_on, a[..., None], axis=-1)[..., 0]
    a_star = jnp.argmax(jax.lax.stop_gradient(q_on), axis=-1)
    q_boot = value_unrescale(
        jnp.take_along_axis(q_tgt, a_star[..., None], axis=-1)[..., 0], eps_h)

    # n-step return of step t: rewards until a terminal, then the bootstrap
    # from step t+n if no terminal fell inside the window
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


def gather(ring, idx, weight):
    """The batch a draw of slots `idx` assembles from host copies of the
    ring's rows."""
    take = lambda name: jnp.asarray(np.asarray(ring[name])[idx])  # noqa: E731
    return {
        "frames": take("frames"), "action": take("actions"),
        "reward": take("rewards"), "done": take("dones"),
        "valid": take("valids"), "init_c": take("init_c"),
        "init_h": take("init_h"), "weight": jnp.asarray(weight),
    }


def stratified_draw(priority, u01):
    """Slot of each stratum's draw, in float64 on the host, with the distance
    of each draw from the nearer edge of its slot (a share of the total): a
    draw nearer than float32's rounding to an edge is ambiguous."""
    p = np.asarray(priority, np.float64)
    total, cdf = p.sum(), np.cumsum(p)
    b = len(u01)
    u = (np.arange(b) + np.asarray(u01, np.float64)) / b * total
    idx = np.clip(np.searchsorted(cdf, u, side="right"), 0, len(p) - 1)
    lo = np.where(idx > 0, cdf[idx - 1], 0.0)
    margin = np.minimum(u - lo, cdf[idx] - u) / total
    return idx.astype(np.int64), margin


def is_weights(priority, idx, n_stored, beta):
    p = np.asarray(priority, np.float64)
    prob = np.maximum(p[idx] / max(p.sum(), 1e-12), 1e-12)
    w = (float(n_stored) * prob) ** (-float(beta))
    return (w / w.max()).astype(np.float32)
