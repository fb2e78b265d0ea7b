"""Plain float32 reference of the DeepSeek-V3-family recurrent core (MLA with
decoupled rotary keys in every layer, a dense SwiGLU in the leading layers,
sparse experts beside shared ones in the rest), written from the layer
equations of ISSUE 32 / PERF.md section 4 after the published
`modeling_deepseek_v3.py`: `jax.numpy` at `highest` matmul precision, no
flax, no window state, no cache, nothing of the program.

One pass over a whole sequence from the empty state, with the published
absolute positions 0..T-1.  `burn` marks the stop-gradient of R2D2's burn-in:
what a step at or after `burn` takes from the steps before it (their latents
and rope keys) carries no gradient, exactly as a burn-in whose final state is
stop-gradiented.  `resets[b, t]` cuts the sequence BEFORE step t: a step
attends only to the steps of its own segment.

Departures from the published code, each because the agent is no language
model or because this chip holds a share of the deployment:
  * no embedding and no LM head: `in_proj` (the trunk's features to the
    hidden size, no bias) stands where the embedding would;
  * a segment mask beside the causal one (the published model has no cuts);
  * the rotation is applied to the pairs in place, by `rope_pairs`; the
    published code (`rope_interleave`) first permutes each vector to
    [evens | odds] and applies `rotate_half`, which is `rope_halves` here:
    query and key are permuted alike, so the scores are the same, and a test
    holds the two to each other;
  * of the routed experts only those `held` = (first, count) are computed;
    what the absent ones would add is left out (the chip's share of an
    expert-parallel layer); the two shared experts are one SwiGLU of twice
    the width, as published;
  * the selection bias (`e_score_correction_bias`, here `select_bias`) is a
    leaf like any other but enters the choice alone, as published; the
    published 1e-20 in the weights' denominator is left out (sigmoid scores
    are positive); `n_group` = `topk_group` = 1: no group limit.

`p` is the core's parameter tree (`params["core"]` of the program's net),
`cc` the core configuration file's dict, `dot(x, w)` the matrix product (the
benchmark's control swaps in a lower-precision one).

This file exists twice, as tests/reference_deepseek_v3_core.py and as
benchmarks/references/deepseek_v3_core.py; a test holds the two to the same
text.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def plain_dot(x, w):
    return jnp.dot(x, w, precision=HI)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def stop_before(z, burn):
    """z [B, T, ...] with no gradient through its first `burn` steps."""
    if burn <= 0:
        return z
    return jnp.concatenate(
        [jax.lax.stop_gradient(z[:, :burn]), z[:, burn:]], axis=1)


def segments(resets):
    """[B, T] int: how many resets fell at or before each step."""
    return jnp.cumsum(resets.astype(jnp.int32), axis=1)


def swiglu(p, x, dot):
    return dot(jax.nn.silu(dot(x, p["gate"]["kernel"]))
               * dot(x, p["up"]["kernel"]), p["down"]["kernel"])


def rope_angles(positions, dim, theta):
    """[T, dim/2]: position x theta^(-2i/dim), i = 0..dim/2-1."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    return positions.astype(jnp.float32)[:, None] * inv_freq[None, :]


def rope_pairs(u, positions, theta):
    """u [B, T, ..., dim]: each adjacent pair (u_2i, u_2i+1) turned by its
    step's angle i."""
    dim = u.shape[-1]
    ang = rope_angles(positions, dim, theta)
    ang = ang.reshape((1, ang.shape[0]) + (1,) * (u.ndim - 3) + (dim // 2,))
    even, odd = u[..., 0::2], u[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     even * jnp.sin(ang) + odd * jnp.cos(ang)], axis=-1)
    return out.reshape(u.shape)


def rope_halves(u, positions, theta):
    """The published form: permute to [evens | odds], then
    u cos + rotate_half(u) sin with the angles repeated over both halves."""
    dim = u.shape[-1]
    u = jnp.concatenate([u[..., 0::2], u[..., 1::2]], axis=-1)
    ang = rope_angles(positions, dim, theta)
    ang = jnp.concatenate([ang, ang], axis=-1)
    ang = ang.reshape((1, ang.shape[0]) + (1,) * (u.ndim - 3) + (dim,))
    half = jnp.concatenate([-u[..., dim // 2:], u[..., : dim // 2]], axis=-1)
    return u * jnp.cos(ang) + half * jnp.sin(ang)


def mla_mixer(p, cc, x, resets, burn, dot, window=None, rope=rope_pairs):
    heads = cc["num_attention_heads"]
    nope, rd = cc["qk_nope_head_dim"], cc["qk_rope_head_dim"]
    dv, rank = cc["v_head_dim"], cc["kv_lora_rank"]
    theta = float(cc["rope_theta"])
    b, t, _ = x.shape
    seg, pos = segments(resets), jnp.arange(t)
    q = dot(x, p["q_proj"]["kernel"]).reshape(b, t, heads, nope + rd)
    kva = dot(x, p["kv_a"]["kernel"])
    c = rms_norm(kva[..., :rank], p["kv_norm"]["scale"], cc["rms_norm_eps"])
    c, k_r = stop_before(c, burn), stop_before(kva[..., rank:], burn)
    kv = dot(c, p["kv_b"]["kernel"]).reshape(b, t, heads, nope + dv)
    q_r = rope(q[..., nope:], pos, theta)
    k_r = rope(k_r[:, :, None, :], pos, theta)  # one rope key for all heads
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, t, heads, rd))], axis=-1)
    q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    v = kv[..., nope:]
    scores = jnp.einsum("bthd,bshd->bhts", q, k, precision=HI) / math.sqrt(
        nope + rd)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    if window is not None:  # the last `window` steps, the step itself included
        causal = causal & (
            jnp.arange(t)[None, :] > jnp.arange(t)[:, None] - window)
    mask = causal[None] & (seg[:, :, None] == seg[:, None, :])
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), v,
                   precision=HI)
    return dot(o.reshape(b, t, heads * dv), p["o_proj"]["kernel"])


def moe_ffn(p, cc, x, held, dot):
    """Router over all experts, the `held` = (first, count) experts computed
    one by one with masks, the shared experts added once."""
    k, first, count = cc["num_experts_per_tok"], held[0], held[1]
    s = jax.nn.sigmoid(dot(x, p["router"]["kernel"]))
    _, idx = jax.lax.top_k(s + p["router"]["select_bias"], k)
    sel = jnp.take_along_axis(s, idx, axis=-1)
    w = sel / sel.sum(axis=-1, keepdims=True) * cc["routed_scaling_factor"]
    y = swiglu(p["shared"], x, dot)
    ex = p["experts"]
    for e in range(count):
        coef = jnp.sum(w * (idx == first + e), axis=-1)
        one = {n: {"kernel": ex[n][e]} for n in ("gate", "up", "down")}
        y = y + coef[..., None] * swiglu(one, x, dot)
    return y


def core_forward(p, cc, x, resets, burn=0, dot=plain_dot, held=None,
                 window=None):
    """x [B, T, features] -> y [B, T, hidden] from the empty state.  With
    `window` a step attends to the last `window` steps only (the actor's
    rolling window; the learn path's sequences are no longer than it)."""
    eps = cc["rms_norm_eps"]
    if held is None:
        held = (cc.get("first_expert_here", 0), cc["experts_here"])
    x = dot(x, p["in_proj"]["kernel"])
    for layer in range(1, cc["layers_here"] + 1):
        lp = p[f"layer_{layer}"]
        h = rms_norm(x, lp["mix_norm"]["scale"], eps)
        x = x + mla_mixer(lp["mla"], cc, h, resets, burn, dot, window)
        h = rms_norm(x, lp["ffn_norm"]["scale"], eps)
        if layer <= cc["first_k_dense_replace"]:
            x = x + swiglu(lp["ffn"], h, dot)
        else:
            x = x + moe_ffn(lp["moe"], cc, h, held, dot)
    return rms_norm(x, p["final_norm"]["scale"], eps)
