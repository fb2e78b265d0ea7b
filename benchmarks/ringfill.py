"""The sequence ring's contents when a run begins, made by the benchmark from
the seed: a replay of deployment size that is full, as it is for all but the
first minutes of a training run.

Every row is a function of (key, row index) alone, so the driver fills the
ring on the device chunk by chunk in one jitted call, and the plain reference
makes the few rows a learn step drew again for itself, without reading the
program's ring.  Rows are shaped like the ones the fused trainer appends on a
game without terminal states (freeway): whole sequences, and one in
`CUT_EVERY` cut short by the time limit, zero-padded and masked from there.
Frames are uniform bytes: the cost of the draw, the gather and the learn step
does not depend on what the frames show, and no two rows are alike.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import ringrows

CUT_EVERY = 6  # freeway cuts at 500 ticks: every sixth sequence of a lane
REWARD_RATE = 0.02  # a crossing every fifty steps or so
STATE_SCALE = 0.25  # stored LSTM states: small, like an actor's early ones
PRIORITY_LOW = 0.05  # priorities uniform in [low, 1]; new rows enter at 1
CHUNK = 64  # rows made at a time while filling (49 MB of frames at 120x80x80)


def _row_key(key, i):
    return jax.random.fold_in(key, i)


def priorities(key, ids):
    """[n] float32: the stored priority (already ^omega) of rows `ids`."""
    def one(i):
        kp = jax.random.split(_row_key(key, i), 6)[5]
        return jax.random.uniform(kp, (), jnp.float32, PRIORITY_LOW, 1.0)

    return jax.vmap(one)(jnp.asarray(ids, jnp.int32))


def rows(key, ids, seq_len, frame_shape, lstm_size, num_actions):
    """Rows `ids` of the seeded ring, under the ring's own field names."""
    h, w = frame_shape
    cut_len = seq_len - seq_len // CUT_EVERY

    def one(i):
        kf, ka, kr, kc, kh, _kp = jax.random.split(_row_key(key, i), 6)
        n_valid = jnp.where(i % CUT_EVERY == CUT_EVERY - 1, cut_len, seq_len)
        valid = jnp.arange(seq_len) < n_valid
        frames = jax.random.bits(kf, (seq_len, h, w), jnp.uint8)
        actions = jax.random.randint(ka, (seq_len,), 0, num_actions, jnp.int32)
        rewards = (jax.random.uniform(kr, (seq_len,)) < REWARD_RATE).astype(
            jnp.float32)
        return {
            "frames": jnp.where(valid[:, None, None], frames, jnp.uint8(0)),
            "actions": jnp.where(valid, actions, 0),
            "rewards": jnp.where(valid, rewards, 0.0),
            "dones": jnp.zeros((seq_len,), bool),
            "valids": valid,
            "init_c": STATE_SCALE * jax.random.normal(kc, (lstm_size,)),
            "init_h": STATE_SCALE * jax.random.normal(kh, (lstm_size,)),
        }

    return jax.vmap(one)(jnp.asarray(ids, jnp.int32))


def fill(replay, state, key, n_rows, num_actions):
    """The replay's `state` with ring rows [0, n_rows) made from the seed;
    traced inside the caller's jit.  The geometry is the replay's own and the
    rows go in through `ringrows.write_rows`, so how the ring stores them is
    not this file's business.  Whole chunks go in place under a loop, the
    rest in one piece."""
    def put(st, ids, start):
        made = rows(key, ids, replay.L, replay.frame_shape, replay.lstm_size,
                    num_actions)
        return ringrows.write_rows(replay, st, made, start)

    n_chunks = n_rows // CHUNK
    state = jax.lax.fori_loop(
        0, n_chunks,
        lambda c, st: put(st, c * CHUNK + jnp.arange(CHUNK), c * CHUNK),
        state)
    done = n_chunks * CHUNK
    if done < n_rows:
        state = put(state, jnp.arange(done, n_rows), done)
    return state
