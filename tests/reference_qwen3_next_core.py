"""Plain float32 reference of the Qwen3-Next recurrent core (Gated DeltaNet in
three layers of four, a gated softmax attention in the fourth, sparse experts
under a softmax router beside a gated shared expert in every layer), written
from the layer equations of ISSUE 35 / PERF.md section 4 after the published
`modeling_qwen3_next.py`: `jax.numpy` at `highest` matmul precision, no flax,
the recurrence step by step, no chunk, no window state, no cache, nothing of
the program.

One pass over a whole sequence from the empty state, with the published
absolute positions 0..T-1.  `burn` marks the stop-gradient of R2D2's burn-in:
what a step at or after `burn` takes from the steps before it (the delta-rule
state, the convolution's tail, the attention's keys and values) carries no
gradient, exactly as a burn-in whose final state is stop-gradiented.
`resets[b, t]` cuts the sequence BEFORE step t: the state is zeroed, and a
step convolves over and attends to the steps of its own segment only.

Departures from the published code, each because the agent is no language
model, because this chip holds a share of the deployment, or because a
seeded kernel has no column order to keep:
  * no embedding and no LM head: `in_proj` (the trunk's features to the
    hidden size, no bias) stands where the embedding would; the multi-token
    prediction head is left out with the tokens;
  * a segment mask beside the causal one, in the convolution and the
    attention (the published model has no cuts);
  * the published `in_proj_qkvz` and `in_proj_ba` order their columns by key
    head ([q k v z] of key head 0, then of head 1, ...; [b a] likewise);
    here the columns stand [q | k | v | z] and [b | a]: a permutation of the
    columns of a kernel whose every column is drawn alike.  The convolution's
    taps are stored [kernel, channels] with tap j on the step j back, where
    the published weight is [channels, 1, kernel] with its last tap on the
    present step: the same reversal and transposition on both sides;
  * the published `Qwen3NextRMSNorm` multiplies by `1 + w` with w = 0 at the
    start; the parameter tree stores that factor as `scale` (1 at the
    start), so `zero_centred_norm` is handed w = scale - 1.  The gated norm
    after the recurrence (`Qwen3NextRMSNormGated`) multiplies by its weight
    as it is, as here;
  * of the routed experts only those `held` = (first, count) are computed;
    what the absent ones would add is left out (the chip's share of an
    expert-parallel layer); the shared expert with its gate is computed
    whole, as every chip computes it;
  * `select_bias` is a leaf the published model does not have: zero on the
    normal path, it enters the choice of experts alone (the benchmark's
    seeded selection lives in it, as in the two other cores).

`p` is the core's parameter tree (`params["core"]` of the program's net),
`cc` the core configuration file's dict, `dot(x, w)` the matrix product (the
benchmark's control swaps in a lower-precision one).

This file exists twice, as tests/reference_qwen3_next_core.py and as
benchmarks/references/qwen3_next_core.py; a test holds the two to the same
text.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6


def plain_dot(x, w):
    return jnp.dot(x, w, precision=HI)


def rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def zero_centred_norm(x, w, eps):
    """The published `Qwen3NextRMSNorm`: rms(x) (1 + w)."""
    return rms(x, eps) * (1.0 + w)


def norm(x, p, eps):
    return zero_centred_norm(x, p["scale"] - 1.0, eps)


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def stop_before(z, burn):
    """z [B, T, ...] with no gradient through its first `burn` steps."""
    if burn <= 0:
        return z
    return jnp.concatenate(
        [jax.lax.stop_gradient(z[:, :burn]), z[:, burn:]], axis=1)


def segments(resets):
    """[B, T] int: how many resets fell at or before each step."""
    return jnp.cumsum(resets.astype(jnp.int32), axis=1)


def short_conv(z, taps, seg):
    """Causal depthwise convolution, kernel len(taps): out_t = sum_j taps[j]
    z_{t-j}, over the steps of t's own segment (zero before the sequence)."""
    out = jnp.zeros_like(z)
    for j in range(taps.shape[0]):
        zj = jnp.pad(z, ((0, 0), (j, 0), (0, 0)))[:, : z.shape[1]]
        sj = jnp.pad(seg, ((0, 0), (j, 0)), constant_values=-1)[:, : seg.shape[1]]
        out = out + taps[j] * zj * (sj == seg)[..., None]
    return out


def swiglu(p, x, dot):
    return dot(jax.nn.silu(dot(x, p["gate"]["kernel"]))
               * dot(x, p["up"]["kernel"]), p["down"]["kernel"])


def gated_delta_net(p, cc, x, resets, burn, dot):
    hk, hv = cc["linear_num_key_heads"], cc["linear_num_value_heads"]
    dk, dv = cc["linear_key_head_dim"], cc["linear_value_head_dim"]
    key_dim, value_dim = hk * dk, hv * dv
    b, t, _ = x.shape
    seg = segments(resets)
    qkvz = dot(x, p["qkvz_proj"]["kernel"])
    ba = dot(x, p["ba_proj"]["kernel"])
    mixed = stop_before(qkvz[..., : 2 * key_dim + value_dim], burn)
    z = qkvz[..., 2 * key_dim + value_dim:].reshape(b, t, hv, dv)
    mixed = jax.nn.silu(short_conv(mixed, p["conv"]["taps"], seg))
    q = l2_norm(mixed[..., :key_dim].reshape(b, t, hk, dk))
    k = l2_norm(mixed[..., key_dim: 2 * key_dim].reshape(b, t, hk, dk))
    v = mixed[..., 2 * key_dim:].reshape(b, t, hv, dv)
    # value head i reads key head i // (hv / hk)
    q, k = jnp.repeat(q, hv // hk, axis=2), jnp.repeat(k, hv // hk, axis=2)
    beta = stop_before(jax.nn.sigmoid(ba[..., :hv]), burn)
    decay = stop_before(jnp.exp(
        -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])),
        burn)  # [B, T, hv]: one scalar a head and step

    def step(s, xs):
        i, q_t, k_t, v_t, a_t, b_t, r_t = xs
        s = jnp.where(r_t[:, None, None, None], 0.0, s)
        s = jnp.where(i == burn, jax.lax.stop_gradient(s), s)
        s = a_t[..., None, None] * s
        ks = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=HI)
        s = s + b_t[..., None, None] * jnp.einsum(
            "bhk,bhv->bhkv", k_t, v_t - ks, precision=HI)
        o_t = jnp.einsum("bhk,bhkv->bhv", q_t, s, precision=HI)
        return s, o_t / math.sqrt(dk)

    mv = lambda u: jnp.moveaxis(u, 1, 0)  # noqa: E731
    s0 = jnp.zeros((b, hv, dk, dv), jnp.float32)
    _, o = jax.lax.scan(
        step, s0,
        (jnp.arange(t), mv(q), mv(k), mv(v), mv(decay), mv(beta), mv(resets)))
    o = rms(jnp.moveaxis(o, 0, 1), cc["rms_norm_eps"]) * p["o_norm"]["scale"]
    return dot((o * jax.nn.silu(z)).reshape(b, t, value_dim),
               p["o_proj"]["kernel"])


def rope_halves(u, positions, dim, theta):
    """u [B, T, H, d]: the first `dim` dimensions of every head turned by the
    step's position, u cos + rotate_half(u) sin with the angles repeated
    over both halves of those dimensions; the rest passes."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    rot, rest = u[..., :dim], u[..., dim:]
    half = jnp.concatenate([-rot[..., dim // 2:], rot[..., : dim // 2]], axis=-1)
    return jnp.concatenate(
        [rot * jnp.cos(ang) + half * jnp.sin(ang), rest], axis=-1)


def gated_attention(p, cc, x, resets, burn, dot, window=None):
    heads, kv_heads, d = (cc["num_attention_heads"], cc["num_key_value_heads"],
                          cc["head_dim"])
    rot, theta = int(d * cc["partial_rotary_factor"]), float(cc["rope_theta"])
    eps = cc["rms_norm_eps"]
    b, t, _ = x.shape
    seg, pos = segments(resets), jnp.arange(t)
    qg = dot(x, p["q_proj"]["kernel"]).reshape(b, t, heads, 2 * d)
    q, gate = norm(qg[..., :d], p["q_norm"], eps), qg[..., d:]
    k = norm(dot(x, p["k_proj"]["kernel"]).reshape(b, t, kv_heads, d),
             p["k_norm"], eps)
    v = dot(x, p["v_proj"]["kernel"]).reshape(b, t, kv_heads, d)
    k, v = stop_before(k, burn), stop_before(v, burn)
    q, k = rope_halves(q, pos, rot, theta), rope_halves(k, pos, rot, theta)
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
    return dot((o * jax.nn.sigmoid(gate)).reshape(b, t, heads * d),
               p["o_proj"]["kernel"])


def moe_ffn(p, cc, x, held, dot):
    """Softmax router over all experts, the `held` = (first, count) experts
    computed one by one with masks, the gated shared expert added once."""
    k, first, count = cc["num_experts_per_tok"], held[0], held[1]
    s = jax.nn.softmax(dot(x, p["router"]["kernel"]), axis=-1)
    _, idx = jax.lax.top_k(s + p["router"]["select_bias"], k)
    sel = jnp.take_along_axis(s, idx, axis=-1)
    w = sel / sel.sum(axis=-1, keepdims=True)
    y = jax.nn.sigmoid(dot(x, p["shared_gate"]["kernel"])) * swiglu(
        p["shared"], x, dot)
    ex = p["experts"]
    for e in range(count):
        coef = jnp.sum(w * (idx == first + e), axis=-1)
        one = {n: {"kernel": ex[n][e]} for n in ("gate", "up", "down")}
        y = y + coef[..., None] * swiglu(one, x, dot)
    return y


def core_forward(p, cc, x, resets, burn=0, dot=plain_dot, held=None,
                 window=None):
    """x [B, T, features] -> y [B, T, hidden] from the empty state.  With
    `window` the attention layers attend to the last `window` steps only (the
    actor's rolling window; the learn path's sequences are no longer than
    it)."""
    eps = cc["rms_norm_eps"]
    if held is None:
        held = (cc.get("first_expert_here", 0), cc["experts_here"])
    x = dot(x, p["in_proj"]["kernel"])
    for layer in range(1, cc["layers_here"] + 1):
        lp = p[f"layer_{layer}"]
        h = norm(x, lp["mix_norm"], eps)
        if layer % cc["full_attention_interval"] == 0:
            x = x + gated_attention(lp["gattn"], cc, h, resets, burn, dot,
                                    window)
        else:
            x = x + gated_delta_net(lp["gdn"], cc, h, resets, burn, dot)
        h = norm(x, lp["ffn_norm"], eps)
        x = x + moe_ffn(lp["moe"], cc, h, held, dot)
    return norm(x, p["final_norm"], eps)
