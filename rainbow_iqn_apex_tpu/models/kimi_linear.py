"""Kimi-Linear as the recurrent core of `R2D2Net` (interface: models/cores.py).

Layers as published for Kimi-Linear-48B-A3B (configs/cores/): pre-norm
residual blocks, a KDA mixer (gated delta rule, a [d_k, d_v] matrix state per
head, short causal convolutions) in three layers of four and an MLA mixer
(latent attention without rotation over a window of latents) in the fourth;
a dense SwiGLU after the first mixer, sparse experts after the others.

This module holds what is Kimi-Linear's alone: the KDA mixer and the reader
of the published keys.  The stack, the MLA mixer, the expert layer and the
other blocks are models/mla_moe.py's, which the DeepSeek-V3 core
(models/deepseek_v3.py) runs too.

Per-lane state, all float32, zero = initial (models/cores.zero_lanes):
  KDA   S [B, H, d_k, d_v] and the convolutions' tails [B, K-1, 3*H*d_k]
  MLA   the window's latents [B, L, rank + rope], their validity [B, L] and
        the ring's head [B] (L slots: `window` for a lane, a ring written one
        slot a tick and reset by its validity; 0 at a sequence's start and
        growing: models/mla_moe.py)

Sequences run the KDA recurrence chunked (`kda_chunked`: WY form, the
in-chunk decay products taken relative to sub-block starts so that no
exponent is positive; on a TPU the in-chunk preparation is a tile kernel,
models/kda_tile.py, of which `_prep_plain` is the definition; a gate one
channel wide, Gated DeltaNet's in models/qwen3_next.py, takes the scalar form
`_prep_scalar` instead, plain matrix products, everywhere); one step
(`T == 1`, the actor) runs it as written.
An episode cut inside a sequence is a segment boundary: steps interact only
within a segment, in the chunk, the convolutions and the attention mask.

The plain reference is tests/reference_kimi_linear_core.py.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from rainbow_iqn_apex_tpu.models import kda_tile
from rainbow_iqn_apex_tpu.models.cores import CORE_STATS as STATS
from rainbow_iqn_apex_tpu.models.mla_moe import (  # noqa: F401  (the blocks' old home)
    HI,
    CoreConfig,
    StackCore,
    _causal_conv,
    _Linear,
    _MLA,
    _mm,
    _MoE,
    _RMSNorm,
    _Stack,
    _Taps,
)
from rainbow_iqn_apex_tpu.obs import device_scopes

L2_EPS = 1e-6


class KimiLinearConfig(CoreConfig):
    """`CoreConfig` read from a `kimi_linear` configuration file."""

    @classmethod
    def from_dict(cls, cc: Dict[str, Any]) -> "KimiLinearConfig":
        la, assumed = cc["linear_attn_config"], cc.get("assumed", {})
        return cls(
            hidden=cc["hidden_size"],
            mixers=tuple(_KDA if i in la["kda_layers"] else _MLA
                         for i in range(1, cc["layers_here"] + 1)),
            first_dense=cc["first_k_dense_replace"], eps=cc["rms_norm_eps"],
            kda_heads=la["num_heads"], kda_dim=la["head_dim"],
            conv_kernel=la["short_conv_kernel_size"],
            low_rank=assumed.get("low_rank", la["head_dim"]),
            chunk=assumed.get("kda_chunk", 40),
            block=assumed.get("kda_block", 8),
            mla_heads=cc["num_attention_heads"], nope=cc["qk_nope_head_dim"],
            rope=cc["qk_rope_head_dim"], v_dim=cc["v_head_dim"],
            kv_rank=cc["kv_lora_rank"],
            window=assumed.get("mla_window", 120),
            dense_width=cc["intermediate_size"], experts=cc["num_experts"],
            top_k=cc["num_experts_per_token"],
            expert_width=cc["moe_intermediate_size"],
            shared_width=cc["moe_intermediate_size"] * cc["num_shared_experts"],
            route_scale=cc["routed_scaling_factor"],
            experts_here=cc["experts_here"],
            first_expert=cc.get("first_expert_here", 0),
        )


def _a_log_init(key, shape):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def _l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


# ------------------------------------------------------------------- KDA
def _decay_pairs(x, y, g_cum, block: int, dtype):
    """[..., C, C]: sum_d x[r, d] y[i, d] exp(G[r, d] - G[i, d]) for i <= r,
    zero above the diagonal.  G (the cumulative log decay) falls with time,
    so the exponent is taken in two non-positive parts about the start of
    r's sub-block where i lies in an earlier one, and pair by pair inside a
    sub-block."""
    *lead, c, d = x.shape
    nb = c // block
    gb = g_cum.reshape(*lead, nb, block, d)
    ref = gb[..., 0, :]  # [..., nb, d]
    xr = x.reshape(*lead, nb, block, d) * jnp.exp(gb - ref[..., None, :])
    yc = y[..., None, :, :] * jnp.exp(jnp.minimum(
        ref[..., :, None, :] - g_cum[..., None, :, :], 0.0))  # [.., nb, C, d]
    off = _mm("...rd,...id->...ri", xr, yc, dtype).reshape(*lead, c, c)
    blk = jnp.arange(c) // block
    off = jnp.where(blk[None, :] < blk[:, None], off, 0.0)
    tri = jnp.tril(jnp.ones((block, block), bool))
    dg = jnp.where(tri[..., None],
                   gb[..., :, None, :] - gb[..., None, :, :], -jnp.inf)
    xb, yb = x.reshape(*lead, nb, block, d), y.reshape(*lead, nb, block, d)
    diag = jnp.sum(
        xb[..., :, None, :] * yb[..., None, :, :] * jnp.exp(dg), axis=-1)
    diag = jnp.einsum("...Iri,IJ->...IrJi", diag, jnp.eye(nb, dtype=diag.dtype))
    return off + diag.reshape(*lead, c, c)


def _chunk_masks(seg, n: int, c: int):
    """seg [B, T] cut into n chunks of c: (seg [B, N, 1, C]; m0 [B, N, 1, C],
    1.0 where the incoming state reaches step r; same [B, N, 1, C, C], steps
    of one segment; lower [C, C], i <= r)."""
    b = seg.shape[0]
    seg = seg.reshape(b, n, 1, c)
    seg_in = jnp.concatenate(
        [jnp.zeros((b, 1, 1), seg.dtype), seg[:, :-1, :, -1]], axis=1)
    m0 = (seg == seg_in[..., None]).astype(jnp.float32)
    same = seg[..., :, None] == seg[..., None, :]
    return seg, m0, same, jnp.tril(jnp.ones((c, c), bool))


def _prep_plain(q, k, v, g, beta, seg, c: int, block: int, dtype):
    """The in-chunk preparation, as written: what the chunk scan reads.

    q, k, g [B, T, H, dk], v [B, T, H, dv], beta [B, T, H], seg [B, T], T a
    multiple of the chunk c.  Returns, chunk-major, u [N, B, H, C, dv] and wk
    [N, B, H, C, dk] (the WY factors: (1 + a) [u | wk] = beta [v | k decayed
    from the chunk's start]), qg, a_qk [N, B, H, C, C], k_end (k decayed to
    the chunk's end) and s_keep [N, B, H, dk] (the state's own decay)."""
    b, t, h, _ = q.shape
    n = t // c
    # [B, N, H, C, .]
    ch = lambda z: jnp.swapaxes(z.reshape(b, n, c, h, -1), 2, 3)  # noqa: E731
    q, k, v, g = ch(q), ch(k), ch(v), ch(g)
    beta = jnp.swapaxes(beta.reshape(b, n, c, h), 2, 3)  # [B, N, H, C]
    seg, m0, same, lower = _chunk_masks(seg, n, c)
    g_cum = jnp.cumsum(g, axis=-2)
    p_kk = _decay_pairs(k, k, g_cum, block, dtype)
    p_qk = _decay_pairs(q, k, g_cum, block, dtype)
    a = beta[..., None] * jnp.where(same & jnp.tril(lower, -1), p_kk, 0.0)
    a_qk = jnp.where(same & lower, p_qk, 0.0)
    decay = jnp.exp(g_cum)
    kg, qg = k * decay * m0[..., None], q * decay * m0[..., None]
    rhs = beta[..., None] * jnp.concatenate([v, kg], axis=-1)
    sol = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(c, dtype=a.dtype), rhs, lower=True, unit_diagonal=True)
    u, wk = sol[..., : v.shape[-1]], sol[..., v.shape[-1]:]
    g_last = g_cum[..., -1:, :]
    k_end = k * jnp.exp(g_last - g_cum) * (
        seg == seg[..., -1:]).astype(jnp.float32)[..., None]
    s_keep = jnp.exp(g_last[..., 0, :]) * m0[..., -1:]  # [B, N, H, dk]
    # the scan multiplies all but u and s_keep on `dtype` operands
    return tuple(jnp.moveaxis(z, 1, 0).astype(dt) for z, dt in (
        (u, jnp.float32), (wk, dtype), (qg, dtype), (a_qk, dtype),
        (k_end, dtype), (s_keep, jnp.float32)))


@functools.partial(jax.checkpoint, static_argnums=(6, 7))
def _prep_scalar(q, k, v, g, beta, seg, c: int, dtype):
    """`_prep_plain` where the gate is one channel wide (g [B, T, H, 1]: one
    log decay a head and step, Gated DeltaNet's).  The exponent then leaves
    the sum over the channels: the decayed pair products are [C, dk] x
    [dk, C] matrix products on `dtype` operands, scaled in float32 by
    exp(G[r] - G[i]) (never positive on or below the diagonal), so no
    sub-block reference is needed; s_keep comes back one wide and the scan
    broadcasts it.  Saves its inputs alone for the backward, as
    `_prep_fused` does."""
    b, t, h, _ = q.shape
    n = t // c
    ch = lambda z: jnp.swapaxes(z.reshape(b, n, c, h, -1), 2, 3)  # noqa: E731
    q, k, v = ch(q), ch(k), ch(v)
    g, beta = ch(g)[..., 0], ch(beta)[..., 0]  # [B, N, H, C]
    seg, m0, same, lower = _chunk_masks(seg, n, c)
    g_cum = jnp.cumsum(g, axis=-1)
    # a select, not a clip: where two steps' sums tie a clip at 0 would pass
    # half the cotangent
    pairs = jnp.exp(jnp.where(
        lower, g_cum[..., :, None] - g_cum[..., None, :], 0.0))
    p_kk = _mm("...rd,...id->...ri", k, k, dtype) * pairs
    p_qk = _mm("...rd,...id->...ri", q, k, dtype) * pairs
    a = beta[..., None] * jnp.where(same & jnp.tril(lower, -1), p_kk, 0.0)
    a_qk = jnp.where(same & lower, p_qk, 0.0)
    decay = jnp.exp(g_cum) * m0
    qg = q * decay[..., None]
    # (1 + a) [u | wk] = beta [v | k decay]: the inverse with the right-hand
    # sides' row scalars folded into its columns, then one float32 product a
    # side, on v and k as they came
    eye = jnp.eye(c, dtype=a.dtype)
    inv = jax.scipy.linalg.solve_triangular(
        a + eye, jnp.broadcast_to(eye, a.shape), lower=True,
        unit_diagonal=True)
    u = jnp.matmul(inv * beta[..., None, :], v, precision=HI)
    wk = jnp.matmul(inv * (beta * decay)[..., None, :], k, precision=HI)
    g_last = g_cum[..., -1:]
    k_end = k * (jnp.exp(g_last - g_cum) * (seg == seg[..., -1:]))[..., None]
    s_keep = jnp.exp(g_last) * m0[..., -1:]  # [B, N, H, 1]
    return tuple(jnp.moveaxis(z, 1, 0).astype(dt) for z, dt in (
        (u, jnp.float32), (wk, dtype), (qg, dtype), (a_qk, dtype),
        (k_end, dtype), (s_keep, jnp.float32)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _prep_fused(q, k, v, g, beta, seg, c, block, dtype):
    """`_prep_plain` by the tile kernel (models/kda_tile.py)."""
    return kda_tile.prepare(q, k, v, g, beta, seg, c, block, dtype)


def _prep_fused_fwd(q, k, v, g, beta, seg, c, block, dtype):
    return (kda_tile.prepare(q, k, v, g, beta, seg, c, block, dtype),
            (q, k, v, g, beta, seg))


def _prep_fused_bwd(c, block, dtype, saved, cot):
    seg = saved[-1]
    return (*kda_tile.prepare_vjp(*saved, cot, c, block, dtype),
            np.zeros(seg.shape, jax.dtypes.float0))


_prep_fused.defvjp(_prep_fused_fwd, _prep_fused_bwd)


def _chunk_len(t: int, chunk: int, block: int) -> int:
    """A sequence of t steps runs in chunks of this many: `chunk`, or a
    short sequence's whole sub-blocks."""
    return chunk if t >= chunk else -(-t // block) * block


def kda_prep_fused(dk: int, dv: int, chunk: int, block: int) -> bool:
    """Whether a sequence's preparation runs as the tile kernel: on a TPU,
    with no mesh that could split the batch (a `pallas_call` has no
    partitioning rule), at shapes the kernel takes.  Otherwise the plain
    path, which is the definition."""
    return (jax.default_backend() == "tpu"
            and jax.sharding.get_abstract_mesh().size <= 1
            and kda_tile.takes(dk, dv, chunk, block))


def kda_prep_path(gate_width: int, dk: int, dv: int, chunk: int,
                  block: int) -> str:
    """Which execution of the in-chunk preparation a sequence takes, by what
    can be observed: "scalar" for a gate one channel wide, else "tile" where
    the kernel runs, else "plain"."""
    if gate_width == 1:
        return "scalar"
    return "tile" if kda_prep_fused(dk, dv, chunk, block) else "plain"


def kda_chunked(q, k, v, g, beta, seg, s0, chunk: int, block: int, dtype):
    """The KDA recurrence over a sequence, chunk by chunk.

    q, k [B, T, H, dk] (l2-normalised), v [B, T, H, dv], g the per-step log
    decay (<= 0), [B, T, H, dk] a key channel (KDA) or [B, T, H, 1] a head
    (Gated DeltaNet), beta [B, T, H], seg [B, T] segment ids (0 = the
    segment `s0` belongs to), s0 [B, H, dk, dv].  The gate's width picks the
    in-chunk preparation: one wide, its scalar form (`_prep_scalar`, plain
    matrix products, on every platform); a channel wide, `_prep_plain` or
    on a TPU the tile kernel.
    Returns (o [B, T, H, dv] before the 1/sqrt(dk), final state)."""
    b, t, h, dk = q.shape
    c = _chunk_len(t, chunk, block)
    pad = -t % c
    if pad:
        zp = lambda z: jnp.pad(z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2))  # noqa: E731
        q, k, v, g, beta = zp(q), zp(k), zp(v), zp(g), zp(beta)
        seg = jnp.pad(seg, ((0, 0), (0, pad)), mode="edge")
    n = (t + pad) // c
    with jax.named_scope(device_scopes.KDA_PREP):
        path = kda_prep_path(g.shape[-1], dk, v.shape[-1], c, block)
        if path == "scalar":
            xs = _prep_scalar(q, k, v, g, beta, seg, c, dtype)
        else:
            prep = _prep_fused if path == "tile" else _prep_plain
            xs = prep(q, k, v, g, beta, seg, c, block, dtype)

    def one(s, xs):
        u_n, wk_n, qg_n, aqk_n, kend_n, keep_n = xs
        w = u_n - _mm("bhck,bhkv->bhcv", wk_n, s, dtype)
        o = _mm("bhck,bhkv->bhcv", qg_n, s, dtype) + _mm(
            "bhcr,bhrv->bhcv", aqk_n, w, dtype)
        s = keep_n[..., None] * s + _mm("bhck,bhcv->bhkv", kend_n, w, dtype)
        return s, o

    s, o = jax.lax.scan(one, s0, xs)
    o = jnp.swapaxes(jnp.moveaxis(o, 0, 1), 2, 3).reshape(b, n * c, h, -1)
    return o[:, :t], s


def kda_step(q, k, v, g, beta, reset, s):
    """One step of the recurrence as written: q, k, v, g [B, H, d], beta
    [B, H], reset [B] bool, s [B, H, dk, dv]."""
    s = jnp.where(reset[:, None, None, None], 0.0, s) * jnp.exp(g)[..., None]
    ks = jnp.einsum("bhk,bhkv->bhv", k, s, precision=HI)
    s = s + beta[..., None, None] * jnp.einsum(
        "bhk,bhv->bhkv", k, v - ks, precision=HI)
    return jnp.einsum("bhk,bhkv->bhv", q, s, precision=HI), s


class _KDA(nn.Module):
    kc: CoreConfig
    compute_dtype: Any

    layer_name = "kda"

    @staticmethod
    def zero_state(kc: CoreConfig, batch: int):
        return {"S": jnp.zeros((batch, kc.kda_heads, kc.kda_dim, kc.kda_dim),
                               jnp.float32),
                "conv": jnp.zeros((batch, kc.conv_kernel - 1,
                                   3 * kc.kda_heads * kc.kda_dim), jnp.float32)}

    @nn.compact
    def __call__(self, x, state, seg):
        kc, cd = self.kc, self.compute_dtype
        b, t, _ = x.shape
        h, dk, kk = kc.kda_heads, kc.kda_dim, kc.conv_kernel
        d = h * dk
        names = ("q", "k", "v")
        with jax.named_scope(device_scopes.KDA_MIX):
            z = jnp.concatenate(
                [_Linear(d, cd, name=f"{n}_proj")(x) for n in names], axis=-1)
            taps = jnp.concatenate(
                [_Taps(kk, d, name=f"{n}_conv")() for n in names], axis=-1)
            conv, tail = _causal_conv(z, taps, state["conv"], seg)
            q, k, v = (y.reshape(b, t, h, dk)
                       for y in jnp.split(jax.nn.silu(conv), 3, axis=-1))
            q, k = _l2_norm(q), _l2_norm(k)
            low = lambda a, bb, n: _Linear(n, cd, name=bb)(  # noqa: E731
                _Linear(kc.low_rank, cd, name=a)(x))
            a_log = self.param("A_log", _a_log_init, (h,))
            dt_bias = self.param("dt_bias", _dt_bias_init, (d,))
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                (low("f_a", "f_b", d) + dt_bias).reshape(b, t, h, dk))
            beta = jax.nn.sigmoid(_Linear(h, cd, name="b_proj")(x))
        if t == 1:
            with jax.named_scope(device_scopes.CORE_STEP):
                o, s = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                beta[:, 0], seg[:, 0] > 0, state["S"])
                o = o[:, None]
        else:
            with jax.named_scope(device_scopes.KDA_SCAN):
                o, s = kda_chunked(q, k, v, g, beta, seg, state["S"],
                                   kc.chunk, kc.block, cd)
            self.sow(STATS, "kda_fused_tile_share", float(kda_prep_path(
                dk, dk, dk, _chunk_len(t, kc.chunk, kc.block),
                kc.block) == "tile"))
        with jax.named_scope(device_scopes.KDA_MIX):
            o = _RMSNorm(kc.eps, name="o_norm")(o / math.sqrt(dk))
            gate = jax.nn.sigmoid(low("g_a", "g_b", d))
            y = _Linear(kc.hidden, cd, name="o_proj")(
                gate * o.reshape(b, t, d))
        return y, {"S": s, "conv": tail}


def _dt_bias_init(key, shape):
    """Inverse softplus of a step size log-uniform in [1e-3, 0.1] (the
    Mamba-2 / KDA initialisation)."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, math.log(1e-3), math.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


@dataclasses.dataclass(frozen=True)
class KimiLinearCore(StackCore):
    kc: CoreConfig
    compute_dtype: Any = jnp.bfloat16

    stat_names = StackCore.moe_stat_names + ("kda_fused_tile_share",)
