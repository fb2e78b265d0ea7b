"""Qwen3-Next as the recurrent core of `R2D2Net` (interface: models/cores.py).

Layers as published for Qwen3-Next-80B-A3B (configs/cores/): pre-norm
residual blocks whose mixer is Gated DeltaNet in three layers of four and a
gated softmax attention in the fourth (`full_attention_interval`), every
feed-forward `num_experts` sparse experts under a softmax router beside one
shared expert weighed by a sigmoid gate.  The trunk's features are not the
model's hidden size and no width is cut, so an input projection stands where
a language model has its embedding.

This module holds what is Qwen3-Next's alone: the two mixers and the reader of
the published keys.  The stack, the expert layer and the other blocks are
models/mla_moe.py's, the delta-rule recurrence (`kda_chunked`, `kda_step`)
models/kimi_linear.py's: Gated DeltaNet decays a head's state by one scalar a
step where KDA decays every key channel by its own, so the recurrence is
handed a gate one channel wide and `kda_chunked` prepares its chunks in the
scalar form (`_prep_scalar`: the decayed pair products are matrix products),
on every platform; KDA's tile kernels do not run for this core.

Gated DeltaNet, of `gdn_key_heads` key heads serving `gdn_value_heads` value
heads (value head i reads key head i // (value heads / key heads)):
  [q | k | v | z] = x W_qkvz, [b | a] = x W_ba; [q | k | v] through ONE causal
  depthwise convolution and silu; q, k l2-normalised a head;
  beta = sigmoid(b), g = -exp(A_log) softplus(a + dt_bias), a scalar a head;
  S <- exp(g) S; S <- S + beta k (v - S^T k)^T; o = S^T q / sqrt(d_k);
  y = [RMSNorm(o_i) silu(z_i)]_i W_o.
The published `in_proj_qkvz` / `in_proj_ba` interleave their columns by key
head ([q k v z] of head 0, then of head 1, ...); here the columns stand
[q | k | v | z] and [b | a], a permutation of columns of a seeded kernel.

Gated attention, of `attn_heads` query heads over `attn_kv_heads` key/value
heads (query head i reads head i // (heads / kv heads)):
  [q_i | gate_i] = x W_q a head; k, v = x W_k, x W_v; q, k RMS-normalised a
  head; the first `attn_rotary_dim` dimensions of every q and k head turned
  by position (`rotate_half` over the halves of those dimensions);
  o_i = softmax(q_i K^T / sqrt(d) + mask) V; y = [o_i sigmoid(gate_i)]_i W_o.

Per-lane state, all float32, zero = initial:
  Gated DeltaNet  S [B, Hv, d_k, d_v] and the convolution's tail
                  [B, K-1, 2 Hk d_k + Hv d_v]; reset by models/cores.zero_lanes
  attention       the window's keys (after their norm, UN-rotated) and values
                  [B, L, Hkv, d] each, their validity [B, L] and the ring's
                  head [B].  `window` slots are a RING (a lane that acts): a
                  tick writes one slot in place and the lane is reset by its
                  slots' validity and head (`window_reset`); fewer are a
                  sequence's window, 0 at its start and growing by its steps
                  (models/mla_moe.py says which is which: the state's shape)
The rotation is applied at use, by a slot's position among the slots attended
over, (slot - head) mod `window` on a ring (models/mla_moe.py says why that is
the published rotation by absolute position).  An episode cut inside a
sequence is a segment boundary: steps interact only within a segment, in the
chunk, the convolution and the attention mask.  One step (`T == 1`, the
actor) runs the recurrence as written and one row of `window` scores.

The published RMSNorm is zero-centred (`1 + w`, w = 0 at the start); its
`1 + w` is stored here as `scale` (1 at the start): the same function.

The plain reference is tests/reference_qwen3_next_core.py.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from flax import linen as nn

from rainbow_iqn_apex_tpu.models.cores import CORE_STATS as STATS
from rainbow_iqn_apex_tpu.models.kimi_linear import (
    _a_log_init,
    _chunk_len,
    _dt_bias_init,
    _l2_norm,
    kda_chunked,
    kda_prep_path,
    kda_step,
)
from rainbow_iqn_apex_tpu.models.mla_moe import (
    NEG,
    CoreConfig,
    StackCore,
    kv_window_zero_state,
    _causal_conv,
    _Linear,
    _mm,
    _RMSNorm,
    _Taps,
    rotate_halves,
    sow_written_share,
    window_mask,
    window_open,
    window_reset,
)
from rainbow_iqn_apex_tpu.obs import device_scopes


class _GatedDeltaNet(nn.Module):
    kc: CoreConfig
    compute_dtype: Any

    layer_name = "gdn"

    @staticmethod
    def zero_state(kc: CoreConfig, batch: int):
        channels = (2 * kc.gdn_key_heads * kc.gdn_key_dim
                    + kc.gdn_value_heads * kc.gdn_value_dim)
        return {"S": jnp.zeros((batch, kc.gdn_value_heads, kc.gdn_key_dim,
                                kc.gdn_value_dim), jnp.float32),
                "conv": jnp.zeros((batch, kc.conv_kernel - 1, channels),
                                  jnp.float32)}

    @nn.compact
    def __call__(self, x, state, seg):
        kc, cd = self.kc, self.compute_dtype
        b, t, _ = x.shape
        hk, hv = kc.gdn_key_heads, kc.gdn_value_heads
        dk, dv = kc.gdn_key_dim, kc.gdn_value_dim
        key_dim, value_dim = hk * dk, hv * dv
        with jax.named_scope(device_scopes.GDN_MIX):
            qkvz = _Linear(2 * key_dim + 2 * value_dim, cd,
                           name="qkvz_proj")(x)
            ba = _Linear(2 * hv, cd, name="ba_proj")(x)
            z = qkvz[..., 2 * key_dim + value_dim:].reshape(b, t, hv, dv)
            conv, tail = _causal_conv(
                qkvz[..., : 2 * key_dim + value_dim],
                _Taps(kc.conv_kernel, 2 * key_dim + value_dim, name="conv")(),
                state["conv"], seg)
            conv = jax.nn.silu(conv)
            q = conv[..., :key_dim].reshape(b, t, hk, dk)
            k = conv[..., key_dim: 2 * key_dim].reshape(b, t, hk, dk)
            v = conv[..., 2 * key_dim:].reshape(b, t, hv, dv)
            q, k = (jnp.repeat(_l2_norm(y), hv // hk, axis=2) for y in (q, k))
            a_log = self.param("A_log", _a_log_init, (hv,))
            dt_bias = self.param("dt_bias", _dt_bias_init, (hv,))
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = (-jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
                 )[..., None]  # [B, T, Hv, 1]: one wide, the scan's scalar form
        if t == 1:
            with jax.named_scope(device_scopes.CORE_STEP):
                o, s = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                beta[:, 0], seg[:, 0] > 0, state["S"])
                o = o[:, None]
        else:
            with jax.named_scope(device_scopes.KDA_SCAN):
                o, s = kda_chunked(q, k, v, g, beta, seg, state["S"],
                                   kc.chunk, kc.block, cd)
            path = kda_prep_path(g.shape[-1], dk, dv,
                                 _chunk_len(t, kc.chunk, kc.block), kc.block)
            self.sow(STATS, "kda_fused_tile_share", float(path == "tile"))
            self.sow(STATS, "kda_scalar_gate_share", float(path == "scalar"))
        with jax.named_scope(device_scopes.GDN_MIX):
            o = _RMSNorm(kc.eps, name="o_norm")(o / math.sqrt(dk))
            y = _Linear(kc.hidden, cd, name="o_proj")(
                (o * jax.nn.silu(z)).reshape(b, t, value_dim))
        return y, {"S": s, "conv": tail}


class _GatedAttention(nn.Module):
    kc: CoreConfig
    compute_dtype: Any

    layer_name = "gattn"

    zero_state = staticmethod(kv_window_zero_state)
    reset_state = staticmethod(window_reset)

    @nn.compact
    def __call__(self, x, state, seg):
        kc, cd = self.kc, self.compute_dtype
        b, t, _ = x.shape
        h, g, d, w = kc.attn_heads, kc.attn_kv_heads, kc.attn_head_dim, kc.window
        rot = kc.attn_rotary_dim
        with jax.named_scope(device_scopes.GATTN_PROJ):
            qg = _Linear(h * 2 * d, cd, name="q_proj")(x).reshape(
                b, t, g, h // g, 2 * d)
            q = _RMSNorm(kc.eps, name="q_norm")(qg[..., :d])
            gate = qg[..., d:]
            k = _RMSNorm(kc.eps, name="k_norm")(
                _Linear(g * d, cd, name="k_proj")(x).reshape(b, t, g, d))
            v = _Linear(g * d, cd, name="v_proj")(x).reshape(b, t, g, d)
        win = window_open(state, {"k": k, "v": v}, seg, w)
        k, v = win.held["k"], win.held["v"]  # [B, S, G, d]

        def rope(u, pos):
            with jax.named_scope(device_scopes.GATTN_ROPE):
                return jnp.concatenate(
                    [rotate_halves(u[..., :rot], pos, kc.rope_theta),
                     u[..., rot:]], axis=-1)

        with jax.named_scope(device_scopes.GATTN_ATTN):
            scores = _mm("btgrd,bsgd->bgrts", rope(q, win.pos_q),
                         rope(k, win.pos_k), cd)
            mask = window_mask(win, seg, w)
            scores = jnp.where(
                mask[:, None, None], scores / math.sqrt(d), NEG)
            o = _mm("bgrts,bsgd->btgrd", jax.nn.softmax(scores, axis=-1), v, cd)
            o = o * jax.nn.sigmoid(gate)
        with jax.named_scope(device_scopes.GATTN_PROJ):
            y = _Linear(kc.hidden, cd, name="o_proj")(o.reshape(b, t, h * d))
        self.sow(STATS, "gattn_live_key_share",
                 jnp.mean(mask, dtype=jnp.float32))
        sow_written_share(self, state, t, w)
        return y, win.state


class Qwen3NextConfig(CoreConfig):
    """`CoreConfig` read from a `qwen3_next` configuration file."""

    @classmethod
    def from_dict(cls, cc: Dict[str, Any]) -> "Qwen3NextConfig":
        assumed = cc.get("assumed", {})
        if cc.get("decoder_sparse_step", 1) != 1 or cc.get("mlp_only_layers"):
            raise ValueError("dense feed-forward layers among the expert "
                             "layers are not written: every layer is sparse")
        if cc.get("rope_scaling") or cc.get("use_sliding_window"):
            raise ValueError("a scaled rotation and a sliding window are "
                             "not written")
        if not cc.get("norm_topk_prob", True):
            raise ValueError("un-normalised expert weights are not written: "
                             "the chosen scores are divided by their sum")
        interval = cc["full_attention_interval"]
        return cls(
            hidden=cc["hidden_size"],
            mixers=tuple(_GatedAttention if i % interval == 0
                         else _GatedDeltaNet
                         for i in range(1, cc["layers_here"] + 1)),
            eps=cc["rms_norm_eps"],
            gdn_key_heads=cc["linear_num_key_heads"],
            gdn_value_heads=cc["linear_num_value_heads"],
            gdn_key_dim=cc["linear_key_head_dim"],
            gdn_value_dim=cc["linear_value_head_dim"],
            conv_kernel=cc["linear_conv_kernel_dim"],
            chunk=assumed.get("delta_chunk", 40),
            block=assumed.get("delta_block", 8),
            attn_heads=cc["num_attention_heads"],
            attn_kv_heads=cc["num_key_value_heads"],
            attn_head_dim=cc["head_dim"],
            attn_rotary_dim=int(cc["head_dim"] * cc["partial_rotary_factor"]),
            window=assumed.get("attn_window", 120),
            rope_theta=float(cc["rope_theta"]),
            experts=cc["num_experts"], top_k=cc["num_experts_per_tok"],
            expert_width=cc["moe_intermediate_size"],
            shared_width=cc["shared_expert_intermediate_size"],
            route="softmax", shared_gate=True,
            experts_here=cc["experts_here"],
            first_expert=cc.get("first_expert_here", 0),
            in_proj=True,
        )


@dataclasses.dataclass(frozen=True)
class Qwen3NextCore(StackCore):
    kc: CoreConfig
    compute_dtype: Any = jnp.bfloat16

    stat_names = StackCore.moe_stat_names + (
        "kda_fused_tile_share", "gattn_live_key_share",
        "kda_scalar_gate_share")
