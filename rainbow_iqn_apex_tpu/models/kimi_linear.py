"""Kimi-Linear as the recurrent core of `R2D2Net` (interface: models/cores.py).

Layers as published for Kimi-Linear-48B-A3B (configs/cores/): pre-norm
residual blocks, a KDA mixer (gated delta rule, a [d_k, d_v] matrix state per
head, short causal convolutions) in three layers of four and an MLA mixer
(latent attention without rotation over a window of latents) in the fourth;
a dense SwiGLU after the first mixer, sparse experts after the others.

Per-lane state, all float32, zero = initial (models/cores.zero_lanes):
  KDA   S [B, H, d_k, d_v] and the convolutions' tails [B, K-1, 3*H*d_k]
  MLA   the window's latents [B, W, rank + rope] and their validity [B, W]

Sequences run the KDA recurrence chunked (`kda_chunked`: WY form, the
in-chunk decay products taken relative to sub-block starts so that no
exponent is positive; on a TPU the in-chunk preparation is a tile kernel,
models/kda_tile.py, of which `_prep_plain` is the definition); one step
(`T == 1`, the actor) runs it as written.
An episode cut inside a sequence is a segment boundary: steps interact only
within a segment, in the chunk, the convolutions and the attention mask.

The expert layer is told which experts it holds (`experts_here` from
`first_expert`): it routes over all of them, sorts the assignments that fell
on its own by expert and runs one grouped (ragged) product per projection.
No capacity: the row buffer is chosen, by the count, among sizes of which the
largest holds every assignment, so no token is ever dropped.  What absent
experts would add is left out (the chip's share of an expert-parallel layer;
tests/test_kimi_linear_core.py adds the shares up to the uncut layer).

The plain reference is tests/reference_kimi_linear_core.py.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from rainbow_iqn_apex_tpu.models import kda_tile
from rainbow_iqn_apex_tpu.models.cores import CORE_STATS as STATS
from rainbow_iqn_apex_tpu.obs import device_scopes

HI = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6
NEG = -1e30
# row buffers of the grouped product, as multiples of the token count; the
# last is top_k, which holds every assignment
EXPERT_ROWS = (0.5, 2.0)


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    hidden: int
    layers: int
    kda_layers: Tuple[int, ...]
    first_dense: int
    eps: float
    kda_heads: int
    kda_dim: int
    conv_kernel: int
    low_rank: int
    chunk: int
    block: int
    mla_heads: int
    nope: int
    rope: int
    v_dim: int
    kv_rank: int
    window: int
    dense_width: int
    experts: int
    top_k: int
    expert_width: int
    shared_width: int
    route_scale: float
    experts_here: int
    first_expert: int

    @classmethod
    def from_dict(cls, cc: Dict[str, Any]) -> "KimiLinearConfig":
        la, assumed = cc["linear_attn_config"], cc.get("assumed", {})
        return cls(
            hidden=cc["hidden_size"], layers=cc["layers_here"],
            kda_layers=tuple(la["kda_layers"]),
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


def _mm(eq: str, a, b, dtype):
    """einsum on `dtype` operands, float32 accumulation."""
    return jnp.einsum(
        eq, a.astype(dtype), b.astype(dtype),
        preferred_element_type=jnp.float32,
        precision=HI if dtype == jnp.float32 else None)


class _Linear(nn.Module):
    features: int
    compute_dtype: Any

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features), jnp.float32)
        return _mm("...i,io->...o", x, kernel, self.compute_dtype)


class _RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + self.eps) * scale


class _Taps(nn.Module):
    kernel: int
    channels: int

    @nn.compact
    def __call__(self):
        bound = 1.0 / math.sqrt(self.kernel)
        return self.param(
            "taps", lambda k, s: jax.random.uniform(k, s, jnp.float32,
                                                    -bound, bound),
            (self.kernel, self.channels))


class _SwiGLU(nn.Module):
    width: int
    compute_dtype: Any

    @nn.compact
    def __call__(self, x):
        lin = lambda n, name: _Linear(n, self.compute_dtype, name=name)  # noqa: E731
        h = jax.nn.silu(lin(self.width, "gate")(x)) * lin(self.width, "up")(x)
        return lin(x.shape[-1], "down")(h)


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
    seg = seg.reshape(b, n, 1, c)
    seg_in = jnp.concatenate(
        [jnp.zeros((b, 1, 1), seg.dtype), seg[:, :-1, :, -1]], axis=1)
    m0 = (seg == seg_in[..., None]).astype(jnp.float32)  # s0 reaches step r
    same = seg[..., :, None] == seg[..., None, :]  # [B, N, 1, C, C]
    lower = jnp.tril(jnp.ones((c, c), bool))
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


def kda_chunked(q, k, v, g, beta, seg, s0, chunk: int, block: int, dtype):
    """The KDA recurrence over a sequence, chunk by chunk.

    q, k [B, T, H, dk] (l2-normalised), v [B, T, H, dv], g [B, T, H, dk] the
    per-step log decay (<= 0), beta [B, T, H], seg [B, T] segment ids (0 =
    the segment `s0` belongs to), s0 [B, H, dk, dv].
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
        prep = (_prep_fused if kda_prep_fused(dk, v.shape[-1], c, block)
                else _prep_plain)
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
    kc: KimiLinearConfig
    compute_dtype: Any

    @nn.compact
    def __call__(self, x, state, seg):
        kc, cd = self.kc, self.compute_dtype
        b, t, _ = x.shape
        h, dk, kk = kc.kda_heads, kc.kda_dim, kc.conv_kernel
        d = h * dk
        names = ("q", "k", "v")
        z = jnp.concatenate(
            [_Linear(d, cd, name=f"{n}_proj")(x) for n in names], axis=-1)
        taps = jnp.concatenate(
            [_Taps(kk, d, name=f"{n}_conv")() for n in names], axis=-1)
        zin = jnp.concatenate([state["conv"], z], axis=1)  # [B, K-1+T, 3d]
        sin = jnp.concatenate(
            [jnp.zeros((b, kk - 1), seg.dtype), seg], axis=1)
        conv = sum(
            taps[j] * zin[:, kk - 1 - j: kk - 1 - j + t]
            * (sin[:, kk - 1 - j: kk - 1 - j + t] == seg)[..., None]
            for j in range(kk))
        tail = zin[:, t:] * (sin[:, t:] == seg[:, -1:])[..., None]
        q, k, v = (y.reshape(b, t, h, dk)
                   for y in jnp.split(jax.nn.silu(conv), 3, axis=-1))
        q, k = _l2_norm(q), _l2_norm(k)
        low = lambda a, bb, n: _Linear(n, cd, name=bb)(  # noqa: E731
            _Linear(kc.low_rank, cd, name=a)(x))
        a_log = self.param(
            "A_log", lambda key, s: jnp.log(
                jax.random.uniform(key, s, jnp.float32, 1.0, 16.0)), (h,))
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
            self.sow(STATS, "kda_fused_tile_share", float(kda_prep_fused(
                dk, dk, _chunk_len(t, kc.chunk, kc.block), kc.block)))
        o = _RMSNorm(kc.eps, name="o_norm")(o / math.sqrt(dk))
        gate = jax.nn.sigmoid(low("g_a", "g_b", d))
        y = _Linear(kc.hidden, cd, name="o_proj")(gate * o.reshape(b, t, d))
        return y, {"S": s, "conv": tail}


def _dt_bias_init(key, shape):
    """Inverse softplus of a step size log-uniform in [1e-3, 0.1] (the
    Mamba-2 / KDA initialisation)."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, math.log(1e-3), math.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


# ------------------------------------------------------------------- MLA
class _MLA(nn.Module):
    kc: KimiLinearConfig
    compute_dtype: Any

    @nn.compact
    def __call__(self, x, state, seg):
        kc, cd = self.kc, self.compute_dtype
        b, t, _ = x.shape
        h, w, rank = kc.mla_heads, kc.window, kc.kv_rank
        q = _Linear(h * (kc.nope + kc.rope), cd, name="q_proj")(x)
        q = q.reshape(b, t, h, kc.nope + kc.rope)
        kva = _Linear(rank + kc.rope, cd, name="kv_a")(x)
        lat = jnp.concatenate(
            [_RMSNorm(kc.eps, name="kv_norm")(kva[..., :rank]),
             kva[..., rank:]], axis=-1)
        lat = jnp.concatenate([state["lat"], lat], axis=1)  # [B, W+T, .]
        seg_all = jnp.concatenate([jnp.zeros((b, w), seg.dtype), seg], axis=1)
        valid = jnp.concatenate(
            [state["valid"], jnp.ones((b, t), jnp.float32)], axis=1)
        kv = _Linear(h * (kc.nope + kc.v_dim), cd, name="kv_b")(
            lat[..., :rank]).reshape(b, w + t, h, kc.nope + kc.v_dim)
        with jax.named_scope(device_scopes.MLA_ATTN):
            scores = (_mm("bthd,bshd->bhts", q[..., : kc.nope],
                          kv[..., : kc.nope], cd)
                      + _mm("bthr,bsr->bhts", q[..., kc.nope:],
                            lat[..., rank:], cd))
            pos_q, pos_k = w + jnp.arange(t)[:, None], jnp.arange(w + t)[None]
            mask = ((pos_k <= pos_q) & (pos_k > pos_q - w))[None] & (
                valid[:, None, :] > 0) & (seg_all[:, None, :] == seg[:, :, None])
            scores = jnp.where(
                mask[:, None], scores / math.sqrt(kc.nope + kc.rope), NEG)
            o = _mm("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1),
                    kv[..., kc.nope:], cd)
        y = _Linear(kc.hidden, cd, name="o_proj")(
            o.reshape(b, t, h * kc.v_dim))
        valid = valid * (seg_all == seg[:, -1:])
        return y, {"lat": lat[:, t:], "valid": valid[:, t:]}


# ---------------------------------------------------------- expert layer
class _Router(nn.Module):
    experts: int

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.experts), jnp.float32)
        bias = self.param("select_bias", nn.initializers.zeros,
                          (self.experts,), jnp.float32)
        # float32 throughout: the choice of experts is discrete
        return jax.nn.sigmoid(jnp.dot(x, kernel, precision=HI)), bias


def _stacked_init(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) / math.sqrt(shape[1])


class _ExpertWeights(nn.Module):
    """The held experts' stacked kernels (gate, up, down)."""

    count: int
    width: int
    hidden: int

    @nn.compact
    def __call__(self):
        n, f, w = self.count, self.hidden, self.width
        return (self.param("gate", _stacked_init, (n, f, w)),
                self.param("up", _stacked_init, (n, f, w)),
                self.param("down", _stacked_init, (n, w, f)))


def _grouped_swiglu(weights, xs, group_sizes, dtype):
    """SwiGLU of rows sorted by expert: one ragged product a projection."""
    gate, up, down = weights
    rd = lambda a, w: jax.lax.ragged_dot(  # noqa: E731
        a.astype(dtype), w.astype(dtype), group_sizes,
        preferred_element_type=jnp.float32,
        precision=HI if dtype == jnp.float32 else None)
    return rd(jax.nn.silu(rd(xs, gate)) * rd(xs, up), down)


class _MoE(nn.Module):
    kc: KimiLinearConfig
    compute_dtype: Any

    @nn.compact
    def __call__(self, x):
        kc = self.kc
        lead, f = x.shape[:-1], x.shape[-1]
        x = x.reshape(-1, f)
        n, k, held_n = x.shape[0], kc.top_k, kc.experts_here
        with jax.named_scope(device_scopes.MOE_ROUTE):
            s, bias = _Router(kc.experts, name="router")(x)
            _, idx = jax.lax.top_k(s + bias, k)
            sel = jnp.take_along_axis(s, idx, axis=-1)
            w = sel / sel.sum(axis=-1, keepdims=True) * kc.route_scale
            local = idx - kc.first_expert
            held = (local >= 0) & (local < held_n)
            key = jnp.where(held, local, held_n).reshape(-1)
            order = jnp.argsort(key, stable=True)  # held first, by expert
            group_sizes = jnp.bincount(key, length=held_n + 1)[:held_n]
            n_held = group_sizes.sum()
            w_sorted = (w * held).reshape(-1)[order]
        weights = _ExpertWeights(held_n, kc.expert_width, f,
                                 name="experts")()
        cd = self.compute_dtype

        def with_rows(rows):
            def run(weights, x, order, w_sorted, group_sizes, n_held):
                tok = order[:rows] // k
                # rows past the held assignments belong to no group: the
                # grouped product leaves them (and, backwards, their input's
                # gradient) unwritten, so both sides are masked
                live = (jnp.arange(rows) < n_held)[:, None]
                xs = jnp.where(live, x.astype(cd)[tok], 0)
                ys = _grouped_swiglu(
                    weights, xs, group_sizes.astype(jnp.int32), cd)
                ys = jnp.where(live, ys * w_sorted[:rows, None], 0.0)
                return jnp.zeros_like(x).at[tok].add(ys)
            return run

        most = n * min(k, held_n)  # rows that hold every assignment
        sizes = [most]
        if most > 1024:  # a few tokens (the actor): one buffer is enough
            sizes = sorted({min(most, int(n * r)) for r in EXPERT_ROWS} | {most})
        with jax.named_scope(device_scopes.MOE_EXPERTS):
            pick = sum((n_held > r).astype(jnp.int32) for r in sizes[:-1])
            y = jax.lax.switch(pick, [with_rows(r) for r in sizes], weights,
                               x, order, w_sorted, group_sizes, n_held)
        with jax.named_scope(device_scopes.MOE_SHARED):
            y = y + _SwiGLU(kc.shared_width, self.compute_dtype,
                            name="shared")(x)
        load = jnp.bincount(idx.reshape(-1), length=kc.experts)
        rows_taken = jnp.asarray(sizes, jnp.int32)[pick]
        self.sow(STATS, "moe_held_assign_share", n_held / (n * k))
        self.sow(STATS, "moe_expert_load_max_over_mean",
                 load.max() / (n * k / kc.experts))
        self.sow(STATS, "moe_tokens_dropped",
                 (n_held - jnp.minimum(n_held, rows_taken)).astype(jnp.float32))
        return y.reshape(*lead, f)


# ----------------------------------------------------------------- stack
class _Layer(nn.Module):
    kc: KimiLinearConfig
    index: int  # 1-based, as linear_attn_config counts
    compute_dtype: Any

    @nn.compact
    def __call__(self, x, state, seg):
        kc, cd = self.kc, self.compute_dtype
        hn = _RMSNorm(kc.eps, name="mix_norm")(x)
        if self.index in kc.kda_layers:
            y, state = _KDA(kc, cd, name="kda")(hn, state, seg)
        else:
            y, state = _MLA(kc, cd, name="mla")(hn, state, seg)
        x = x + y
        hn = _RMSNorm(kc.eps, name="ffn_norm")(x)
        if self.index <= kc.first_dense:
            x = x + _SwiGLU(kc.dense_width, cd, name="ffn")(hn)
        else:
            x = x + _MoE(kc, cd, name="moe")(hn)
        return x, state


class _Stack(nn.Module):
    kc: KimiLinearConfig
    compute_dtype: Any

    @nn.compact
    def __call__(self, x, state, resets):
        kc = self.kc
        if x.shape[-1] != kc.hidden:
            raise ValueError(
                f"the core's hidden size is {kc.hidden} and the trunk feeds "
                f"it {x.shape[-1]} features: there is no projection between "
                f"them (80x80 frames give 2,304)")
        seg = jnp.cumsum(resets.astype(jnp.int32), axis=1)
        new_state = {}
        for i in range(1, kc.layers + 1):
            layer = nn.remat(_Layer)(kc, i, self.compute_dtype,
                                     name=f"layer_{i}")
            with jax.named_scope(device_scopes.CORE_LAYER):
                x, new_state[f"layer_{i}"] = layer(
                    x, state[f"layer_{i}"], seg)
        return _RMSNorm(kc.eps, name="final_norm")(x), new_state


@dataclasses.dataclass(frozen=True)
class KimiLinearCore:
    kc: KimiLinearConfig
    compute_dtype: Any = jnp.bfloat16

    stored_width = 0  # zero start state: the ring stores no state
    stat_names = ("moe_expert_load_max_over_mean", "moe_held_assign_share",
                  "moe_tokens_dropped", "kda_fused_tile_share")

    def initial_state(self, batch: int):
        kc, z = self.kc, lambda *s: jnp.zeros((batch, *s), jnp.float32)  # noqa: E731
        d = kc.kda_heads * kc.kda_dim
        return {
            f"layer_{i}": (
                {"S": z(kc.kda_heads, kc.kda_dim, kc.kda_dim),
                 "conv": z(kc.conv_kernel - 1, 3 * d)}
                if i in kc.kda_layers else
                {"lat": z(kc.window, kc.kv_rank + kc.rope),
                 "valid": z(kc.window)})
            for i in range(1, kc.layers + 1)}

    def to_stored(self, state):
        b = jax.tree.leaves(state)[0].shape[0]
        return (jnp.zeros((b, 0), jnp.float32), jnp.zeros((b, 0), jnp.float32))

    def from_stored(self, init_c, init_h):
        return self.initial_state(init_c.shape[0])

    def __call__(self, x, state, resets):
        return _Stack(self.kc, self.compute_dtype, name="core")(
            x, state, resets)
