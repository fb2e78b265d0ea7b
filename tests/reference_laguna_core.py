"""Plain float32 reference of the Laguna recurrent core (grouped-query
attention of two kinds side by side, a sliding-window layer of 64 query heads
turned whole by a plain rotation and a full layer of 48 query heads turned by
half under a YaRN-scaled one, a sigmoid gate a query head, a dense SwiGLU in
the leading layer and sigmoid-routed sparse experts beside one shared expert
in the rest), written from the layer equations of ISSUE 47 / PERF.md section
4: `jax.numpy` at `highest` matmul precision, no flax, a loop over layers,
scores over the whole sequence under a mask written from its three
conditions, no window state, no ring, no blocks, no cache, nothing of the
program.

One pass over a whole sequence from the empty state, with the published
absolute positions 0..T-1.  `burn` marks the stop-gradient of R2D2's burn-in:
what a step at or after `burn` takes from the steps before it (their keys and
values) carries no gradient, exactly as a burn-in whose final state is
stop-gradiented.  `resets[b, t]` cuts the sequence BEFORE step t: a step
attends to the steps of its own segment only.

The config.json of Laguna-XS.2 is all that is published here (no modelling
code was read), so what it does not state is `assumed`, in the configuration
file and here alike:
  (a) `gating` true is a gate a query head (the sibling configuration
      Laguna-S-2.1 writes the key as "per-head"): g = sigmoid(x W_g), W_g
      [hidden, heads] a projection of its own of the layer's normed input,
      multiplied into the head's attention output before the output
      projection (the gated-attention form of arXiv:2505.06708);
  (b) no norm on q or k (the config names none);
  (c) the router's scores are sigmoids; the 8 chosen by score (plus the
      selection bias, zero unless seeded), their scores divided by their sum
      and times `moe_routed_scaling_factor`, weighing the experts' OUTPUTS
      (`moe_apply_router_weight_on_input` false); the shared expert is added
      whole, with no gate;
  (d) YaRN as the `transformers` library computes `rope_type` "yarn": the
      frequencies theta^(-2i/d) and those divided by `factor`, blended by a
      linear ramp between the pair indices where `beta_fast` and `beta_slow`
      rotations fit into `original_max_position_embeddings` positions
      (floor and ceiling, clamped to [0, d - 1]), d the ROTATED dimensions
      (`partial_rotary_factor` x head_dim, the head's first d; the rest pass
      through), cos and sin times `attention_factor`; `rotate_half` over
      those d.
Departures from a language model, each because the agent is none or because
this chip holds a share of the deployment:
  * no embedding and no LM head: `in_proj` (the trunk's features to the
    hidden size, no bias) stands where the embedding would;
  * the layers held are `layers_here` of the published ones from
    `first_layer_here` (0-based): their kinds are `layer_types`, their query
    heads `num_attention_heads_per_layer`, their feed-forwards
    `mlp_layer_types` over that range;
  * a segment mask beside the causal one and the band (the published model
    has no cuts);
  * a full layer attends over the whole sequence unless told a `window` (the
    agent's memory, which the learn path's sequences are no longer than); a
    sliding layer over the last `sliding_window` steps, itself included, or
    `window` where that is shorter;
  * of the routed experts only those `held` = (first, count) are computed;
    what the absent ones would add is left out (the chip's share of an
    expert-parallel layer); the shared expert is whole on every chip.

`p` is the core's parameter tree (`params["core"]` of the program's net),
`cc` the core configuration file's dict, `dot(x, w)` the matrix product (the
benchmark's control swaps in a lower-precision one).  `ignore_span` runs the
sliding layers as full ones: the benchmark's second control, which a
comparison that sees the band has to fail.

This file exists twice, as tests/reference_laguna_core.py and as
benchmarks/references/laguna_core.py; a test holds the two to the same text.
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


def inv_frequencies(rp, head_dim):
    """(the rotated dimensions d, their d / 2 frequencies, the factor on cos
    and sin) of one kind's `rope_parameters`; (d) above."""
    d = int(head_dim * rp.get("partial_rotary_factor", 1))
    base = float(rp["rope_theta"])
    plain = [1.0 / base ** (2 * i / d) for i in range(d // 2)]
    if rp.get("rope_type", "default") == "default":
        return d, plain, 1.0
    factor, original = rp["factor"], rp["original_max_position_embeddings"]

    def correction_dim(rotations):
        return d * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(plain):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        extrapolation = 1.0 - ramp  # 1: the pair keeps its frequency
        out.append(f / factor * (1.0 - extrapolation) + f * extrapolation)
    return d, out, float(rp["attention_factor"])


def rope(u, positions, rp):
    """u [B, T, H, hd]: the first d dimensions of every head turned by the
    step's position, u cos + rotate_half(u) sin over those d with the angles
    repeated over both halves, cos and sin times the factor; the rest as they
    are."""
    d, inv_freq, factor = inv_frequencies(rp, u.shape[-1])
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    turned, passed = u[..., :d], u[..., d:]
    half = jnp.concatenate(
        [-turned[..., d // 2:], turned[..., : d // 2]], axis=-1)
    turned = turned * (jnp.cos(ang) * factor) + half * (jnp.sin(ang) * factor)
    return jnp.concatenate([turned, passed], axis=-1)


def attention(p, cc, kind, heads, x, resets, burn, dot, span=None):
    """One attention layer of `kind` (a key of `rope_parameters`) with
    `heads` query heads; `span` None attends over the whole sequence."""
    kv_heads, d = cc["num_key_value_heads"], cc["head_dim"]
    rp = cc["rope_parameters"][kind]
    b, t, _ = x.shape
    seg, pos = segments(resets), jnp.arange(t)
    q = dot(x, p["q_proj"]["kernel"]).reshape(b, t, heads, d)
    k = dot(x, p["k_proj"]["kernel"]).reshape(b, t, kv_heads, d)
    v = dot(x, p["v_proj"]["kernel"]).reshape(b, t, kv_heads, d)
    gate = jax.nn.sigmoid(dot(x, p["g_proj"]["kernel"]))  # [B, T, heads]
    k, v = stop_before(k, burn), stop_before(v, burn)
    q, k = rope(q, pos, rp), rope(k, pos, rp)
    # query head i reads key/value head i // (heads / kv_heads)
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, k, precision=HI) / math.sqrt(d)
    # key j is visible to query t iff j <= t, t - j < span, same episode
    visible = pos[None, :] <= pos[:, None]
    if span is not None:
        visible = visible & (pos[:, None] - pos[None, :] < span)
    mask = visible[None] & (seg[:, :, None] == seg[:, None, :])
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), v,
                   precision=HI)
    return dot((o * gate[..., None]).reshape(b, t, heads * d),
               p["o_proj"]["kernel"])


def moe_ffn(p, cc, x, held, dot):
    """Router over all experts, the `held` = (first, count) experts computed
    one by one with masks, the shared expert added whole."""
    k, first, count = cc["num_experts_per_tok"], held[0], held[1]
    s = jax.nn.sigmoid(dot(x, p["router"]["kernel"]))
    _, idx = jax.lax.top_k(s + p["router"]["select_bias"], k)
    sel = jnp.take_along_axis(s, idx, axis=-1)
    w = sel / sel.sum(axis=-1, keepdims=True) * cc["moe_routed_scaling_factor"]
    y = swiglu(p["shared"], x, dot)
    ex = p["experts"]
    for e in range(count):
        coef = jnp.sum(w * (idx == first + e), axis=-1)
        one = {n: {"kernel": ex[n][e]} for n in ("gate", "up", "down")}
        y = y + coef[..., None] * swiglu(one, x, dot)
    return y


def core_forward(p, cc, x, resets, burn=0, dot=plain_dot, held=None,
                 window=None, ignore_span=False):
    """x [B, T, features] -> y [B, T, hidden] from the empty state.  With
    `window` (the agent's memory) no layer attends further back than that."""
    eps, first_layer = cc["rms_norm_eps"], cc.get("first_layer_here", 0)
    if held is None:
        held = (cc.get("first_expert_here", 0), cc["experts_here"])
    x = dot(x, p["in_proj"]["kernel"])
    for i in range(cc["layers_here"]):
        lp, layer = p[f"layer_{i + 1}"], first_layer + i
        kind = cc["layer_types"][layer]
        span = window
        if kind == "sliding_attention" and not ignore_span:
            span = min(cc["sliding_window"], window or cc["sliding_window"])
        h = rms_norm(x, lp["mix_norm"]["scale"], eps)
        x = x + attention(
            lp["gqa"], cc, kind, cc["num_attention_heads_per_layer"][layer],
            h, resets, burn, dot, span)
        h = rms_norm(x, lp["ffn_norm"]["scale"], eps)
        if cc["mlp_layer_types"][layer] == "dense":
            x = x + swiglu(lp["ffn"], h, dot)
        else:
            x = x + moe_ffn(lp["moe"], cc, h, held, dot)
    return rms_norm(x, p["final_norm"]["scale"], eps)
