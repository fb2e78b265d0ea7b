"""Seeded weights, made by the benchmark and handed to the program and to the
plain reference alike.

The program's own initialisers are not used: the reference may take nothing
the program made.  The tree's names and shapes come from `jax.eval_shape` of
the program's init (shapes only); every leaf is then filled here, on the
device, in one jitted call from the seed, in float32 (the type the trainers
keep their parameters in).  Distributions follow the published initialisers:
noisy layers uniform(+-1/sqrt(in)) for mu and sigma0/sqrt(in) for sigma
(Fortunato et al.), every other kernel normal with variance 1/fan_in, biases
zero.  (flax draws conv and dense kernels from a truncated normal and LSTM
recurrent kernels orthogonal, both of the same variance; recorded under
`assumed` in the configuration files.)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _fill(name: str, shape, fan_in: int, key, sigma0: float):
    if name in ("w_sigma", "b_sigma"):
        return jnp.full(shape, sigma0 / math.sqrt(fan_in), jnp.float32)
    if name in ("w_mu", "b_mu"):
        bound = 1.0 / math.sqrt(fan_in)
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if name == "kernel":
        return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
    if name == "bias":
        return jnp.zeros(shape, jnp.float32)
    raise ValueError(f"no initialiser for a parameter named {name!r}")


def make_params(shapes, key, sigma0: float):
    """`shapes`: nested dict of ShapeDtypeStructs.  Returns the same tree of
    float32 arrays; jit this with `key` as the only argument."""

    def walk(node, key):
        out = {}
        names = sorted(node)
        for i, name in enumerate(names):
            child, k = node[name], jax.random.fold_in(key, i)
            if isinstance(child, dict):
                out[name] = walk(child, k)
                continue
            ref = node.get("w_mu", node.get("kernel"))
            fan_in = math.prod(ref.shape[:-1])
            out[name] = _fill(name, child.shape, fan_in, k, sigma0)
        return out

    return walk(shapes, key)


def as_plain(tree):
    """Nested plain dicts (flax may hand back FrozenDicts)."""
    if hasattr(tree, "items"):
        return {k: as_plain(v) for k, v in tree.items()}
    return tree
