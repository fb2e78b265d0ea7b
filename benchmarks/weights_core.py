"""Seeded weights of a net whose recurrent core is not the LSTM: the core's
leaves are filled here, the trunk and the heads by `weights.make_params`.

As in weights.py, the program's initialisers are not used and every leaf is
float32, made on the device from the seed.  By leaf name:

  kernel       normal(0, 1/fan_in); fan_in is the input width ([in, out])
  gate/up/down the held experts' stacked kernels [experts, in, out]: the same,
               fan_in = in
  scale        ones (RMSNorm)
  taps         uniform(+-1/sqrt(kernel size)): the short convolutions
  A_log        log of uniform(1, 16), a head; dt_bias the inverse softplus of
               a step size log-uniform in [1e-3, 0.1], a channel (the
               Mamba-2 / KDA initialisation: a step's decay lies between
               exp(-16 x 0.1) and exp(-0.001) before the input moves it)
  select_bias  the router's selection bias, a seeded leaf that enters the
               choice of experts alone: SELECT on `top_k` experts of a layer
               (which ones, from the seed) and 0 on the others, so the bias
               decides the choice as a trained router's decides its own, and
               neither the seed nor the learn steps move it.  Of all the
               layers' chosen experts the chip's share (held / experts) are
               held ones, one a layer from the first expert layer on: the
               even share.  Why not seeded scores: an untrained router's
               choice collapses within a few learn steps of R2D2's Adam (the
               residual stream's common component grows until every token of
               a layer picks the same experts), and whether a held expert is
               among them, step by step, made the cell's work go by the seed
               (PERF.md section 6, PR 27)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks import weights

CORE = "core"
SELECT = 2.0  # sigmoid scores lie in (0, 1): a bias of 2 outranks any of them


def _fill(name: str, shape, key):
    if name == "kernel":
        return jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[0])
    if name in ("gate", "up", "down"):
        return jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[1])
    if name == "scale":
        return jnp.ones(shape, jnp.float32)
    if name == "taps":
        bound = 1.0 / math.sqrt(shape[0])
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(0.1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "select_bias":  # `_select` fills it, a layer at a time
        return jnp.zeros(shape, jnp.float32)
    raise ValueError(f"no initialiser for a core parameter named {name!r}")


def _walk(node, key):
    out = {}
    for i, name in enumerate(sorted(node)):
        child, k = node[name], jax.random.fold_in(key, i)
        out[name] = (_walk(child, k) if isinstance(child, dict)
                     else _fill(name, child.shape, k))
    return out


def _select(key, experts: int, top_k: int, first: int, held: int,
            held_chosen: int):
    """[experts] float32: SELECT on `top_k` experts drawn from the seed, of
    which `held_chosen` lie in [first, first + held) and the rest outside."""
    is_held = (jnp.arange(experts) >= first) & (jnp.arange(experts) < first + held)
    u = jax.random.uniform(key, (experts,))
    _, inside = jax.lax.top_k(jnp.where(is_held, u, -1.0), held_chosen)
    _, outside = jax.lax.top_k(jnp.where(is_held, -1.0, u), top_k - held_chosen)
    chosen = jnp.concatenate([inside, outside])
    return jnp.zeros((experts,), jnp.float32).at[chosen].set(SELECT)


def make_params(shapes, key, sigma0: float, top_k: int, first_expert: int = 0):
    """`shapes`: nested dict of ShapeDtypeStructs with the core under
    `core`.  Returns the same tree of float32 arrays.  `top_k` experts a
    token and the first held expert's index are the core configuration's; how
    many experts a layer holds is read off its stacked kernels."""
    rest = {n: v for n, v in shapes.items() if n != CORE}
    params = weights.make_params(rest, key, sigma0)
    params[CORE] = _walk(shapes[CORE], jax.random.fold_in(key, 7))
    layers = sorted((n for n, v in params[CORE].items() if "moe" in v),
                    key=lambda n: int(n.rsplit("_", 1)[1]))
    k_sel = jax.random.fold_in(key, 8)
    for j, name in enumerate(layers):
        moe = params[CORE][name]["moe"]
        experts = moe["router"]["select_bias"].shape[0]
        held = moe["experts"]["gate"].shape[0]
        # the even share of all chosen slots, dealt one a layer from the first
        slots = round(len(layers) * top_k * held / experts)
        here = min(slots // len(layers) + (j < slots % len(layers)),
                   top_k, held)
        moe["router"]["select_bias"] = _select(
            jax.random.fold_in(k_sel, j), experts, top_k, first_expert, held,
            here)
    return params
