"""Ouro (a looped, weight-shared dense transformer) as the recurrent core of
`R2D2Net` (interface: models/cores.py).

Layers as published for Ouro-2.6B (configs/cores/): the layers held here are
run `total_ut_steps` times over the SAME parameters, the final norm after
every pass feeding the next; a block norms the input AND the output of each
sub-layer,
  x <- x + N2(Attn(N1(x))),   x <- x + N4(SwiGLU(N3(x))),
its mixer plain multi-head attention and every feed-forward a dense SwiGLU.
The trunk's features are not the model's hidden size and no width is cut, so
an input projection stands where a language model has its embedding.  The
exit gate (a linear and a sigmoid on each pass's output, beside the LM head
whose per-pass loss it weighs) is left out with the tokens: every step runs
every pass, as the published `early_exit_threshold` 1 has it.

This module holds what is Ouro's alone: the mixer and the reader of the
published keys.  The stack with its passes, the block with its four norms and
the SwiGLU are models/mla_moe.py's, as are the window's mask and the
rotation.

Attention, of `attn_heads` query heads over `attn_kv_heads` key/value heads
(query head i reads head i // (heads / kv heads); the published model has as
many of one as of the other):
  q, k, v = x W_q, x W_k, x W_v, no bias, no norm, no gate; every dimension of
  every q and k head turned by position (`rotate_half` over the head's two
  halves); o_i = softmax(q_i K^T / sqrt(d) + mask) V; y = [o_i]_i W_o.
A family whose attention is this one with an RMSNorm a head on q and k before
the rotation says `attn_qk_norm` (models/lfm2.py: 32 query heads over 8
key/value heads); the window then keeps the keys after their norm.  Ouro's
tree has no such leaf.

Per-lane state, float32, zero = initial, one a (pass, layer), as the
published model keeps a key/value cache for each: a pass's keys are
projections of that pass's input.  The window's keys, UN-rotated, and values
[B, L, Hkv, d] each, their validity [B, L] and the ring's head [B].  The
state's shape says what a window is (models/mla_moe.py): `window` slots are a
RING, what a lane that acts holds; a tick writes its one step over the oldest
slot in place and attends over the ring, one row of `window` scores a (pass,
layer), and a lane is reset by its slots' validity and its head
(`window_reset` through `StackCore.reset_lanes`), what the slots held left
where it is.  Fewer slots are a sequence the learner unrolls: none at its
start, then the steps written, in position order, up to `window`.  The
rotation is applied at use, by a slot's position among the slots attended
over, (slot - head) mod `window` on a ring (models/mla_moe.py says why that
is the published rotation by absolute position).  An episode cut inside a
sequence is a segment boundary.

The plain reference is tests/reference_ouro_core.py.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from flax import linen as nn

from rainbow_iqn_apex_tpu.models.cores import CORE_STATS as STATS
from rainbow_iqn_apex_tpu.models.mla_moe import (
    NEG,
    CoreConfig,
    StackCore,
    kv_window_zero_state,
    _Linear,
    _mm,
    _RMSNorm,
    rotate_halves,
    sow_written_share,
    window_mask,
    window_open,
    window_reset,
)
from rainbow_iqn_apex_tpu.obs import device_scopes


class _MHA(nn.Module):
    kc: CoreConfig
    compute_dtype: Any

    layer_name = "mha"

    zero_state = staticmethod(kv_window_zero_state)
    reset_state = staticmethod(window_reset)

    @nn.compact
    def __call__(self, x, state, seg):
        kc, cd = self.kc, self.compute_dtype
        b, t, _ = x.shape
        h, g, d, w = kc.attn_heads, kc.attn_kv_heads, kc.attn_head_dim, kc.window
        with jax.named_scope(device_scopes.MHA_PROJ):
            q = _Linear(h * d, cd, name="q_proj")(x).reshape(
                b, t, g, h // g, d)
            k = _Linear(g * d, cd, name="k_proj")(x).reshape(b, t, g, d)
            v = _Linear(g * d, cd, name="v_proj")(x).reshape(b, t, g, d)
            if kc.attn_qk_norm:  # over the head's d, one scale for all heads
                q = _RMSNorm(kc.eps, name="q_norm")(q)
                k = _RMSNorm(kc.eps, name="k_norm")(k)
        win = window_open(state, {"k": k, "v": v}, seg, w)
        k, v = win.held["k"], win.held["v"]  # [B, S, G, d]
        with jax.named_scope(device_scopes.MHA_ATTN):
            with jax.named_scope(device_scopes.MHA_ROPE):
                q = rotate_halves(q, win.pos_q, kc.rope_theta)
                k_at = rotate_halves(k, win.pos_k, kc.rope_theta)
            scores = _mm("btgrd,bsgd->bgrts", q, k_at, cd)
            mask = window_mask(win, seg, w)
            scores = jnp.where(
                mask[:, None, None], scores / math.sqrt(d), NEG)
            o = _mm("bgrts,bsgd->btgrd", jax.nn.softmax(scores, axis=-1), v, cd)
        with jax.named_scope(device_scopes.MHA_PROJ):
            y = _Linear(kc.hidden, cd, name="o_proj")(o.reshape(b, t, h * d))
        self.sow(STATS, "attn_live_key_share",
                 jnp.mean(mask, dtype=jnp.float32))
        sow_written_share(self, state, t, w)
        return y, win.state


class OuroConfig(CoreConfig):
    """`CoreConfig` read from an `ouro` configuration file."""

    @classmethod
    def from_dict(cls, cc: Dict[str, Any]) -> "OuroConfig":
        assumed = cc.get("assumed", {})
        layers = cc["layers_here"]
        if cc.get("rope_scaling") or cc.get("use_sliding_window"):
            raise ValueError("a scaled rotation and a sliding window are "
                             "not written")
        if set(cc.get("layer_types", ())[:layers]) - {"full_attention"}:
            raise ValueError("a layer that is not full attention is not "
                             "written")
        if cc.get("early_exit_threshold", 1) < 1:
            raise ValueError("an exit before the last pass is not written: "
                             "every step runs every pass")
        return cls(
            hidden=cc["hidden_size"], mixers=(_MHA,) * layers,
            eps=cc["rms_norm_eps"], passes=cc["total_ut_steps"],
            out_norms=True, first_dense=layers,
            dense_width=cc["intermediate_size"],
            attn_heads=cc["num_attention_heads"],
            attn_kv_heads=cc["num_key_value_heads"],
            attn_head_dim=cc["head_dim"],
            window=assumed.get("attn_window", 120),
            rope_theta=float(cc["rope_theta"]), in_proj=True,
        )


@dataclasses.dataclass(frozen=True)
class OuroCore(StackCore):
    kc: CoreConfig
    compute_dtype: Any = jnp.bfloat16

    stat_names = ("attn_live_key_share", "loop_passes")
