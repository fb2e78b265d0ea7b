"""Plain float32 reference of the Ouro recurrent core (a looped dense
transformer: the layers held here run `total_ut_steps` times over the same
parameters, plain multi-head attention with every head fully rotated, a dense
SwiGLU, four norms a block), written from the layer equations of ISSUE 40 /
PERF.md section 4 after the published `modeling_ouro.py`: `jax.numpy` at
`highest` matmul precision, no flax, a loop over passes inside which a loop
over layers, no window state, no cache, nothing of the program.

One pass over a whole sequence from the empty state, with the published
absolute positions 0..T-1; every (pass, layer) attends over the keys and
values it projected itself from that pass's input, which is what the
published key/value cache of `total_ut_steps x layers` entries holds.  `burn`
marks the stop-gradient of R2D2's burn-in: what a step at or after `burn`
takes from the steps before it (their keys and values, in every pass)
carries no gradient, exactly as a burn-in whose final state is
stop-gradiented.  `resets[b, t]` cuts the sequence BEFORE step t: a step
attends only to the steps of its own segment.

Departures from the published code, each because the agent is no language
model:
  * no embedding and no LM head: `in_proj` (the trunk's features to the
    hidden size, no bias) stands where the embedding would;
  * the exit gate (`early_exit_gate`, a linear and a sigmoid on each pass's
    output, which weighs the per-pass cross-entropy in training and lets a
    token leave early at inference) is left out with the LM head it runs
    beside: the agent has no tokens, and at the published
    `early_exit_threshold` 1 no token leaves before the last pass; the
    core's output is the last pass's, after the final norm;
  * a segment mask beside the causal one (the published model has no cuts);
  * the block's four norms stand under the names the program's tree gives
    them: `mix_norm` and `mix_out_norm` (the published `input_layernorm` and
    `input_layernorm_2`, before and after attention), `ffn_norm` and
    `ffn_out_norm` (`post_attention_layernorm` and
    `post_attention_layernorm_2`, before and after the SwiGLU).

`p` is the core's parameter tree (`params["core"]` of the program's net),
`cc` the core configuration file's dict, `dot(x, w)` the matrix product (the
benchmark's control swaps in a lower-precision one).  `layer_of(r, l)` gives
the parameters of layer l in pass r (both 1-based): the tree's `layer_<l>`
whatever the pass, unless a test hands in untied copies.

This file exists twice, as tests/reference_ouro_core.py and as
benchmarks/references/ouro_core.py; a test holds the two to the same text.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def plain_dot(x, w):
    return jnp.dot(x, w, precision=HI)


def norm(x, p, eps):
    """The published `OuroRMSNorm`: x / rms(x) times the weight."""
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * p["scale"]


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
    heads, kv_heads, d = (cc["num_attention_heads"], cc["num_key_value_heads"],
                          cc["head_dim"])
    theta = float(cc["rope_theta"])
    b, t, _ = x.shape
    seg, pos = segments(resets), jnp.arange(t)
    q = dot(x, p["q_proj"]["kernel"]).reshape(b, t, heads, d)
    k = dot(x, p["k_proj"]["kernel"]).reshape(b, t, kv_heads, d)
    v = dot(x, p["v_proj"]["kernel"]).reshape(b, t, kv_heads, d)
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


def core_forward(p, cc, x, resets, burn=0, dot=plain_dot, window=None,
                 layer_of=None):
    """x [B, T, features] -> y [B, T, hidden] from the empty state.  With
    `window` every (pass, layer) attends to the last `window` steps only (the
    actor's rolling windows; the learn path's sequences are no longer than
    they)."""
    eps = cc["rms_norm_eps"]
    if layer_of is None:
        layer_of = lambda r, l: p[f"layer_{l}"]  # noqa: E731
    x = dot(x, p["in_proj"]["kernel"])
    for r in range(1, cc["total_ut_steps"] + 1):
        for l in range(1, cc["layers_here"] + 1):  # noqa: E741
            lp = layer_of(r, l)
            y = attention(lp["mha"], cc, norm(x, lp["mix_norm"], eps), resets,
                          burn, dot, window)
            x = x + norm(y, lp["mix_out_norm"], eps)
            y = swiglu(lp["ffn"], norm(x, lp["ffn_norm"], eps), dot)
            x = x + norm(y, lp["ffn_out_norm"], eps)
        x = norm(x, p["final_norm"], eps)
    return x
