"""Plain float32 reference of the LFM2 recurrent core (a gated short
convolution in three layers of four, grouped-query attention with an RMSNorm
a head on q and k in the fourth, a dense SwiGLU in the leading layers and
sigmoid-routed sparse experts with an expert bias and NO shared expert in the
rest), written from the layer equations of ISSUE 43 / PERF.md section 4 after
the published `modeling_lfm2_moe.py`: `jax.numpy` at `highest` matmul
precision, no flax, a loop over layers, the convolution as shifted sums, no
window state, no cache, nothing of the program.

One pass over a whole sequence from the empty state, with the published
absolute positions 0..T-1.  `burn` marks the stop-gradient of R2D2's burn-in:
what a step at or after `burn` takes from the steps before it (their keys and
values, and the gated inputs `B * u` that the convolution's taps reach back
to) carries no gradient, exactly as a burn-in whose final state is
stop-gradiented.  `resets[b, t]` cuts the sequence BEFORE step t: a step
attends to, and convolves over, the steps of its own segment only.

Departures from the published code, each because the agent is no language
model or because this chip holds a share of the deployment:
  * no embedding and no LM head: `in_proj` (the trunk's features to the
    hidden size, no bias) stands where the embedding would; the final norm is
    the published `embedding_norm`;
  * the layers held are `layers_here` of the published ones from
    `first_layer_here` (0-based): their kinds are `layer_types` over that
    range, and a layer is dense while its published index is under
    `num_dense_layers`;
  * a segment mask beside the causal one, in the convolution too (the
    published model has no cuts);
  * the short convolution's in-projection columns stand [B | C | u] (the
    published `chunk(3)` order) and its depthwise kernel as `taps` [K,
    channel] in the order of the lag: c_t = sum_j taps[j] z_{t-j}.  The
    published `conv.weight` [channel, 1, K] is applied as a cross-correlation
    over an input padded K-1 to the left, so its tap K-1 meets z_t: `taps[j]`
    is `weight[:, 0, K-1-j]`, a permutation of a seeded leaf;
  * of the routed experts only those `held` = (first, count) are computed;
    what the absent ones would add is left out (the chip's share of an
    expert-parallel layer); there is no shared expert, so a token none of
    whose chosen experts is held gets nothing from the layer;
  * the expert bias (`expert_bias`, here `select_bias`) is a leaf like any
    other but enters the choice alone, as published; the published 1e-6 in
    the weights' denominator is left out, here and in the program (the
    chosen sigmoid scores sum to far more: under 1e-6 of the weights).

`p` is the core's parameter tree (`params["core"]` of the program's net),
`cc` the core configuration file's dict, `dot(x, w)` the matrix product (the
benchmark's control swaps in a lower-precision one).

This file exists twice, as tests/reference_lfm2_core.py and as
benchmarks/references/lfm2_core.py; a test holds the two to the same text.
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


def short_conv(p, cc, x, resets, burn, dot):
    """The gated short convolution: y = (C * conv(B * u)) W_out.  A step's
    own term keeps its gradient; what the taps reach back to across the
    burn-in's end does not (it is the state the burn-in handed on)."""
    t = x.shape[1]
    seg = segments(resets)
    b_gate, c_gate, u = jnp.split(dot(x, p["in_proj"]["kernel"]), 3, axis=-1)
    z = b_gate * u
    past = stop_before(z, burn)
    taps = p["conv"]["taps"]
    conv = taps[0] * z
    for lag in range(1, cc["conv_L_cache"]):
        # z_{t-lag} of the step's own segment, zeros before the sequence
        back = jnp.pad(past, ((0, 0), (lag, 0), (0, 0)))[:, :t]
        same = jnp.pad(seg, ((0, 0), (lag, 0)), constant_values=-1)[:, :t] == seg
        conv = conv + taps[lag] * back * same[..., None]
    return dot(c_gate * conv, p["out_proj"]["kernel"])


def rope(u, positions, theta):
    """u [B, T, H, d]: every head turned whole by the step's position,
    u cos + rotate_half(u) sin with the angles repeated over both halves."""
    d = u.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    half = jnp.concatenate([-u[..., d // 2:], u[..., : d // 2]], axis=-1)
    return u * jnp.cos(ang) + half * jnp.sin(ang)


def attention(p, cc, x, resets, burn, dot, window=None):
    heads, kv_heads = cc["num_attention_heads"], cc["num_key_value_heads"]
    d = cc.get("head_dim") or cc["hidden_size"] // heads
    theta, eps = float(cc["rope_theta"]), cc["norm_eps"]
    b, t, _ = x.shape
    seg, pos = segments(resets), jnp.arange(t)
    q = dot(x, p["q_proj"]["kernel"]).reshape(b, t, heads, d)
    k = dot(x, p["k_proj"]["kernel"]).reshape(b, t, kv_heads, d)
    v = dot(x, p["v_proj"]["kernel"]).reshape(b, t, kv_heads, d)
    # the published q_layernorm / k_layernorm: over the head's d, before the
    # rotation, one weight for all heads
    q = rms_norm(q, p["q_norm"]["scale"], eps)
    k = rms_norm(k, p["k_norm"]["scale"], eps)
    k, v = stop_before(k, burn), stop_before(v, burn)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    # query head i reads key/value head i // (heads / kv_heads)
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, k, precision=HI) / math.sqrt(d)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    if window is not None:  # the last `window` steps, the step itself included
        causal = causal & (
            jnp.arange(t)[None, :] > jnp.arange(t)[:, None] - window)
    mask = causal[None] & (seg[:, :, None] == seg[:, None, :])
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), v,
                   precision=HI)
    return dot(o.reshape(b, t, heads * d), p["o_proj"]["kernel"])


def moe_ffn(p, cc, x, held, dot):
    """Router over all experts, the `held` = (first, count) experts computed
    one by one with masks; nothing else is added."""
    k, first, count = cc["num_experts_per_tok"], held[0], held[1]
    s = jax.nn.sigmoid(dot(x, p["router"]["kernel"]))
    _, idx = jax.lax.top_k(s + p["router"]["select_bias"], k)
    sel = jnp.take_along_axis(s, idx, axis=-1)
    w = sel / sel.sum(axis=-1, keepdims=True) * cc["routed_scaling_factor"]
    y = jnp.zeros_like(x)
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
    eps, first_layer = cc["norm_eps"], cc.get("first_layer_here", 0)
    if held is None:
        held = (cc.get("first_expert_here", 0), cc["experts_here"])
    x = dot(x, p["in_proj"]["kernel"])
    for i in range(cc["layers_here"]):
        lp = p[f"layer_{i + 1}"]
        h = rms_norm(x, lp["mix_norm"]["scale"], eps)
        if cc["layer_types"][first_layer + i] == "conv":
            x = x + short_conv(lp["sconv"], cc, h, resets, burn, dot)
        else:
            x = x + attention(lp["mha"], cc, h, resets, burn, dot, window)
        h = rms_norm(x, lp["ffn_norm"]["scale"], eps)
        if first_layer + i < cc["num_dense_layers"]:
            x = x + swiglu(lp["ffn"], h, dot)
        else:
            x = x + moe_ffn(lp["moe"], cc, h, held, dot)
    return rms_norm(x, p["final_norm"]["scale"], eps)
