"""Plain float32 reference of the Kimi-Linear recurrent core (KDA matrix
state, an MLA layer, dense and sparse-expert SwiGLU), written from the layer
equations of ISSUE 27 / PERF.md section 4: `jax.numpy` at `highest` matmul
precision, no flax, no chunks, no window bookkeeping, nothing of the program.

One pass over a whole sequence from the zero state.  `burn` marks the
stop-gradient of R2D2's burn-in: what a step at or after `burn` takes from
the steps before it (the KDA state, the short convolutions' tails, the MLA
latents) carries no gradient, exactly as a burn-in whose final state is
stop-gradiented.  `resets[b, t]` zeroes the state BEFORE step t.

`p` is the core's parameter tree (`params["core"]` of the program's net),
`cc` the core configuration file's dict, `dot(x, w)` the matrix product (the
benchmark's control swaps in a lower-precision one).

This file exists twice, as tests/reference_kimi_linear_core.py and as
benchmarks/references/kimi_linear_core.py; a test holds the two to the same
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


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


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


def kda_mixer(p, cc, x, resets, burn, dot):
    la = cc["linear_attn_config"]
    heads, dk = la["num_heads"], la["head_dim"]
    b, t, _ = x.shape
    seg = segments(resets)

    def branch(name, act):
        z = stop_before(dot(x, p[f"{name}_proj"]["kernel"]), burn)
        z = act(short_conv(z, p[f"{name}_conv"]["taps"], seg))
        return z.reshape(b, t, heads, dk)

    q = l2_norm(branch("q", jax.nn.silu))
    k = l2_norm(branch("k", jax.nn.silu))
    v = branch("v", jax.nn.silu)
    f = dot(dot(x, p["f_a"]["kernel"]), p["f_b"]["kernel"]) + p["dt_bias"]
    a = jnp.exp(-jnp.exp(p["A_log"])[:, None]
                * jax.nn.softplus(f.reshape(b, t, heads, dk)))
    a = stop_before(a, burn)
    beta = stop_before(jax.nn.sigmoid(dot(x, p["b_proj"]["kernel"])), burn)

    def step(s, xs):
        i, q_t, k_t, v_t, a_t, b_t, r_t = xs
        s = jnp.where(r_t[:, None, None, None], 0.0, s)
        s = jnp.where(i == burn, jax.lax.stop_gradient(s), s)
        s = a_t[..., None] * s
        ks = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=HI)
        s = s + b_t[..., None, None] * jnp.einsum(
            "bhk,bhv->bhkv", k_t, v_t - ks, precision=HI)
        o_t = jnp.einsum("bhk,bhkv->bhv", q_t, s, precision=HI)
        return s, o_t / math.sqrt(dk)

    mv = lambda z: jnp.moveaxis(z, 1, 0)  # noqa: E731
    s0 = jnp.zeros((b, heads, dk, dk), jnp.float32)
    _, o = jax.lax.scan(
        step, s0,
        (jnp.arange(t), mv(q), mv(k), mv(v), mv(a), mv(beta), mv(resets)))
    o = rms_norm(jnp.moveaxis(o, 0, 1), p["o_norm"]["scale"],
                 cc["rms_norm_eps"])
    gate = jax.nn.sigmoid(dot(dot(x, p["g_a"]["kernel"]), p["g_b"]["kernel"]))
    return dot(gate * o.reshape(b, t, heads * dk), p["o_proj"]["kernel"])


def mla_mixer(p, cc, x, resets, burn, dot):
    heads = cc["num_attention_heads"]
    nope, rope = cc["qk_nope_head_dim"], cc["qk_rope_head_dim"]
    dv, rank = cc["v_head_dim"], cc["kv_lora_rank"]
    b, t, _ = x.shape
    seg = segments(resets)
    q = dot(x, p["q_proj"]["kernel"]).reshape(b, t, heads, nope + rope)
    kva = dot(x, p["kv_a"]["kernel"])
    c = rms_norm(kva[..., :rank], p["kv_norm"]["scale"], cc["rms_norm_eps"])
    c, k_r = stop_before(c, burn), stop_before(kva[..., rank:], burn)
    kv = dot(c, p["kv_b"]["kernel"]).reshape(b, t, heads, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_r[:, :, None, :], (b, t, heads, rope))], axis=-1)
    v = kv[..., nope:]
    scores = jnp.einsum("bthd,bshd->bhts", q, k, precision=HI) / math.sqrt(
        nope + rope)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    mask = causal[None] & (seg[:, :, None] == seg[:, None, :])
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), v,
                   precision=HI)
    return dot(o.reshape(b, t, heads * dv), p["o_proj"]["kernel"])


def moe_ffn(p, cc, x, held, dot):
    """Router over all experts, the `held` = (first, count) experts computed
    one by one with masks, the shared expert added once."""
    k, first, count = cc["num_experts_per_token"], held[0], held[1]
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


def core_forward(p, cc, x, resets, burn=0, dot=plain_dot, held=None):
    """x [B, T, hidden] -> y [B, T, hidden] from the zero state."""
    la, eps = cc["linear_attn_config"], cc["rms_norm_eps"]
    if held is None:
        held = (cc.get("first_expert_here", 0), cc["experts_here"])
    for layer in range(1, cc["layers_here"] + 1):
        lp = p[f"layer_{layer}"]
        h = rms_norm(x, lp["mix_norm"]["scale"], eps)
        if layer in la["kda_layers"]:
            x = x + kda_mixer(lp["kda"], cc, h, resets, burn, dot)
        else:
            x = x + mla_mixer(lp["mla"], cc, h, resets, burn, dot)
        h = rms_norm(x, lp["ffn_norm"]["scale"], eps)
        if layer <= cc["first_k_dense_replace"]:
            x = x + swiglu(lp["ffn"], h, dot)
        else:
            x = x + moe_ffn(lp["moe"], cc, h, held, dot)
    return rms_norm(x, p["final_norm"]["scale"], eps)
