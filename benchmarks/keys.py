"""The key chain of the fused trainers, re-derived without importing them.

`train_anakin_fused` and `train_anakin_r2d2` split `PRNGKey(seed)` into
(loop key, init key, env key), then split the loop key once per segment; the
jitted segment splits its key once per tick, each tick into (act, env, learn),
the learn key once per learn step of the tick, and each learn step's key into
(sample, learn).  The drivers walk the host half of this chain exactly as the
trainers do (benchmarks/tests pin that bit for bit); the plain references walk
all of it to reach the draws and the noise of one learn step.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp


def root_keys(seed: int):
    """(loop key, init key, env key) as both trainers derive them."""
    key, k_init, k_env = jax.random.split(jax.random.PRNGKey(seed), 3)
    return key, k_init, k_env


def learn_key(segment_key, tick: int, ticks: int, learn_index: int,
              learns_per_tick: int):
    """(sample key, learn key) of learn step `learn_index` of tick `tick`."""
    k_tick = jax.random.split(segment_key, ticks)[tick]
    _ka, _ks, kl = jax.random.split(k_tick, 3)
    kk = jax.random.split(kl, learns_per_tick)[learn_index]
    k_sample, k_learn = jax.random.split(kk)
    return k_sample, k_learn


def flax_rng(key, *suffix):
    """The key flax's `make_rng` hands a module: the collection's key with the
    sha1 of (module path..., call count) folded in.  `suffix` is the path of
    submodule names followed by the 1-based count of `make_rng` calls."""
    m = hashlib.sha1()
    for x in suffix:
        if isinstance(x, str):
            m.update(x.encode("utf-8"))
        else:
            m.update(int(x).to_bytes((int(x).bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(
        key, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))
