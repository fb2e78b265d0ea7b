"""Plain float32 building blocks of the references: `jax.numpy` at `highest`
matmul precision, no flax, nothing of the program.

`mode` selects the arithmetic of every matmul and convolution, for the
control of `correct` (benchmarks/tests/test_correct.py), which put in the
program's place has to come out as not correct.  The configurations state
bfloat16 operands under float32 accumulation; the control is the next
precision down, in the forward and in the backward pass alike:

  None    float32 throughout (the reference).
  "fp8"   both operands of every product in float8_e4m3fn and, in the
          backward pass, the incoming gradient in float8_e5m2, each scaled a
          tensor to the type's largest; float32 accumulation.  The usual fp8
          training recipe.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.keys import flax_rng

HI = jax.lax.Precision.HIGHEST


# mode -> (operand type, gradient type), each with its largest finite value
MODES = {"fp8": ((jnp.float8_e4m3fn, 448.0), (jnp.float8_e5m2, 57344.0))}


def _round_to(x, grid):
    """x on `grid` (a float type and its largest value), under one scale
    for the whole tensor."""
    dtype, top = grid
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def lowered(op, mode):
    """`op(x, w)` (bilinear) in the mode's arithmetic: operands on the
    operand grid, the backward pass's products on operands and an incoming
    gradient rounded likewise, every sum in float32."""
    if mode is None:
        return op
    operand, gradient = MODES[mode]

    @jax.custom_vjp
    def f(x, w):
        return op(_round_to(x, operand), _round_to(w, operand))

    def fwd(x, w):
        qx, qw = _round_to(x, operand), _round_to(w, operand)
        return op(qx, qw), (qx, qw)

    def bwd(res, dy):
        return jax.vjp(op, *res)[1](_round_to(dy, gradient))

    f.defvjp(fwd, bwd)
    return f


def dot(x, w, mode):
    return lowered(lambda a, b: jnp.dot(a, b, precision=HI), mode)(x, w)


def conv_trunk(p, x, mode):
    """DQN trunk 32x8x8/4, 64x4x4/2, 64x3x3/1, VALID, NHWC; x float in [0,1].
    Returns [N, features] flattened in (row, col, channel) order."""
    for i, stride in enumerate((4, 2, 1)):
        layer = p[f"Conv_{i}"]
        conv = lambda a, k, s=stride: jax.lax.conv_general_dilated(  # noqa: E731
            a, k, (s, s), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)
        x = jax.nn.relu(lowered(conv, mode)(x, layer["kernel"]) + layer["bias"])
    return x.reshape(x.shape[0], -1)


def _f(x):
    return jnp.sign(x) * jnp.sqrt(jnp.abs(x))


def noisy_linear(p, x, noise_key, name, mode):
    """Factorised-Gaussian noisy layer with the noise flax would draw for the
    module called `name` under the "noise" collection key `noise_key`."""
    n_in, n_out = p["w_mu"].shape
    k_in, k_out = jax.random.split(flax_rng(noise_key, name, 1))
    eps_in = _f(jax.random.normal(k_in, (n_in,), jnp.float32))
    eps_out = _f(jax.random.normal(k_out, (n_out,), jnp.float32))
    y = dot(x, p["w_mu"], mode) + dot(x * eps_in, p["w_sigma"], mode) * eps_out
    return y + p["b_mu"] + p["b_sigma"] * eps_out


def dueling_heads(params, feat, noise_key, mode):
    """[N, F] -> [N, A] dueling noisy heads."""

    def head(name):
        h = jax.nn.relu(noisy_linear(
            params[f"{name}_hidden"], feat, noise_key, f"{name}_hidden", mode))
        return noisy_linear(
            params[f"{name}_out"], h, noise_key, f"{name}_out", mode)

    value, adv = head("value"), head("advantage")
    return value + adv - adv.mean(axis=-1, keepdims=True)


def huber(u, kappa=1.0):
    a = jnp.abs(u)
    return jnp.where(a <= kappa, 0.5 * u * u, kappa * (a - 0.5 * kappa))


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(tree)))


def adam_step(params, grads, m, v, t, *, lr, eps, clip):
    """optax.chain(clip_by_global_norm, adam) written out.  t is 1-based."""
    gn = global_norm(grads)
    scale = jnp.where(gn < clip, 1.0, clip / gn) if clip > 0 else 1.0
    g = jax.tree.map(lambda x: x * scale, grads)
    b1, b2 = 0.9, 0.999
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    params = jax.tree.map(
        lambda p, a, b: p - lr * (a / (1 - b1 ** t)) / (
            jnp.sqrt(b / (1 - b2 ** t)) + eps),
        params, m, v)
    return params, m, v
