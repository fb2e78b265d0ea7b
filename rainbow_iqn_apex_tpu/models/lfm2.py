"""LFM2 (gated short convolutions, grouped-query attention and sparse experts)
as the recurrent core of `R2D2Net` (interface: models/cores.py).

Layers as published for LFM2-8B-A1B (configs/cores/): pre-norm residual
blocks (`operator_norm`, `ffn_norm`: here `mix_norm`, `ffn_norm`) whose mixer
is a gated short convolution (`conv`) in three layers of four and softmax
attention of 32 query heads over 8 key/value heads, with an RMSNorm a head on
q and k, in the fourth (`full_attention`); a dense SwiGLU in the
`num_dense_layers` leading layers, `num_experts` sparse experts under a
sigmoid router with an expert bias in the rest, and NO shared expert: an
expert layer's output is its chosen experts' alone.  The file says which of
the published layers are held here (`layers_here` from `first_layer_here`,
0-based): the mixers are `layer_types` over that range, and of the leading
dense layers those that fall in it stay dense.  The trunk's features are not
the model's hidden size and no width is cut, so an input projection stands
where a language model has its embedding; the final norm is the published
`embedding_norm`.

This module holds what is LFM2's alone: the short-convolution mixer and the
reader of the published keys.  The stack, the expert layer, the SwiGLU, the
convolution with its taps, the window's mask and the rotation are
models/mla_moe.py's; the attention mixer is models/ouro.py's `_MHA`, told to
norm q and k (`attn_qk_norm`).  Neither the delta-rule scan nor its kernels
are imported.

Gated short convolution (the published `Lfm2MoeShortConv`; no activation, no
bias):
  [B | C | u] = x W_in (hidden -> 3 hidden); z = B * u;
  c_t = sum_{j < K} taps_j z_{t-j} (depthwise, causal, K = `conv_L_cache`,
  within the step's own segment); y = (C * c) W_out.
The published depthwise kernel is stored [channel, 1, K] and applied as a
cross-correlation over a left-padded input, so its tap K-1 meets z_t: here
`taps` [K, channel] stands in the order of the lag j, that kernel reversed
and transposed, a permutation of a seeded leaf.

Per-lane state, all float32, zero = initial:
  short convolution  the tail: the last K-1 steps of z, [B, K-1, hidden];
                     reset by models/cores.zero_lanes
  attention          the window's keys (after their norm, UN-rotated) and
                     values [B, L, Hkv, d] each, their validity [B, L] and
                     the ring's head [B].  `window` slots are a RING (a lane
                     that acts): a tick writes one slot in place and the lane
                     is reset by its slots' validity and head; fewer are a
                     sequence's window, 0 at its start and growing by its
                     steps (models/mla_moe.py; models/ouro.py's `_MHA`)
An episode cut inside a sequence is a segment boundary: steps interact only
within a segment, in the convolution (a step reads no z from before the cut)
and in the attention mask.  One step (`T == 1`, the actor) is one K-tap sum a
convolution layer and one row of scores.

The plain reference is tests/reference_lfm2_core.py.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
from flax import linen as nn

from rainbow_iqn_apex_tpu.models.mla_moe import (
    CoreConfig,
    StackCore,
    _causal_conv,
    _Linear,
    _Taps,
)
from rainbow_iqn_apex_tpu.models.ouro import _MHA
from rainbow_iqn_apex_tpu.obs import device_scopes


class _ShortConv(nn.Module):
    kc: CoreConfig
    compute_dtype: Any

    layer_name = "sconv"

    @staticmethod
    def zero_state(kc: CoreConfig, batch: int):
        return {"conv": jnp.zeros((batch, kc.conv_kernel - 1, kc.hidden),
                                  jnp.float32)}

    @nn.compact
    def __call__(self, x, state, seg):
        kc, cd = self.kc, self.compute_dtype
        with jax.named_scope(device_scopes.SCONV_MIX):
            b_gate, c_gate, u = jnp.split(
                _Linear(3 * kc.hidden, cd, name="in_proj")(x), 3, axis=-1)
            conv, tail = _causal_conv(
                b_gate * u, _Taps(kc.conv_kernel, kc.hidden, name="conv")(),
                state["conv"], seg)
            y = _Linear(kc.hidden, cd, name="out_proj")(c_gate * conv)
        return y, {"conv": tail}


_MIXERS = {"conv": _ShortConv, "full_attention": _MHA}


class Lfm2Config(CoreConfig):
    """`CoreConfig` read from an `lfm2_moe` configuration file."""

    @classmethod
    def from_dict(cls, cc: Dict[str, Any]) -> "Lfm2Config":
        assumed = cc.get("assumed", {})
        first = cc.get("first_layer_here", 0)
        kinds = cc["layer_types"][first: first + cc["layers_here"]]
        if len(kinds) != cc["layers_here"] or set(kinds) - set(_MIXERS):
            raise ValueError(
                f"layers {first} to {first + cc['layers_here']} of "
                f"layer_types are {kinds}: a layer that is neither conv nor "
                f"full_attention is not written")
        if cc.get("conv_bias") or cc.get("rope_scaling"):
            raise ValueError("a bias on the short convolution and a scaled "
                             "rotation are not written")
        if not cc.get("norm_topk_prob", True):
            raise ValueError("un-normalised expert weights are not written: "
                             "the chosen scores are divided by their sum")
        heads = cc["num_attention_heads"]
        return cls(
            hidden=cc["hidden_size"], mixers=tuple(_MIXERS[k] for k in kinds),
            eps=cc["norm_eps"],
            first_dense=max(cc["num_dense_layers"] - first, 0),
            dense_width=cc["intermediate_size"],
            conv_kernel=cc["conv_L_cache"],
            attn_heads=heads, attn_kv_heads=cc["num_key_value_heads"],
            attn_head_dim=cc.get("head_dim") or cc["hidden_size"] // heads,
            attn_qk_norm=True,
            window=assumed.get("attn_window", 120),
            rope_theta=float(cc["rope_theta"]),
            experts=cc["num_experts"], top_k=cc["num_experts_per_tok"],
            expert_width=cc["moe_intermediate_size"],
            shared_width=0,  # no shared expert
            route_scale=cc["routed_scaling_factor"],
            experts_here=cc["experts_here"],
            first_expert=cc.get("first_expert_here", 0),
            in_proj=True,
        )


@dataclasses.dataclass(frozen=True)
class Lfm2Core(StackCore):
    kc: CoreConfig
    compute_dtype: Any = jnp.bfloat16

    stat_names = StackCore.moe_stat_names + (
        "attn_live_key_share", "moe_row_fill_share")
