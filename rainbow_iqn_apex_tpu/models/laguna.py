"""Laguna (sliding-window and full attention layers side by side, a gate a
query head, sparse experts beside a shared expert) as the recurrent core of
`R2D2Net` (interface: models/cores.py).

Layers as published for Laguna-XS.2 (configs/cores/): pre-norm residual blocks
whose mixer is grouped-query softmax attention over 8 key/value heads of 128,
of two kinds that differ in everything but those: a `full_attention` layer has
48 query heads, attends over the whole context and turns HALF of each head
(`partial_rotary_factor` 0.5) by a YaRN-scaled rotation; a
`sliding_attention` layer has 64 query heads, attends over the last
`sliding_window` steps and turns every dimension by a plain rotation
(`rope_parameters`, a kind each; `num_attention_heads_per_layer`).  The
feed-forward is a dense SwiGLU where `mlp_layer_types` says `dense` (the
leading layer) and `num_experts` sparse experts under a sigmoid router beside
one shared expert in the rest.  The file says which of the published layers
are held here (`layers_here` from `first_layer_here`, 0-based).  The trunk's
features are not the model's hidden size and no width is cut, so an input
projection stands where a language model has its embedding.

This module holds what is Laguna's alone: the mixer, YaRN's table of
frequencies and the reader of the published keys.  The stack, the expert
layer with its shared expert, the SwiGLU, the window (a ring for a lane that
acts), the rotation by a table and the attention by blocks are
models/mla_moe.py's.

A LAYER'S ATTENTION GEOMETRY IS ITS MIXER'S OWN (`AttnGeometry`: kind, query
heads, span, rotation): `gated_gqa(geometry)` makes the mixer class of a
kind, and the stack's `CoreConfig` keeps only what the kinds share (the
key/value heads, the head's size) and `window`, the agent's memory: the span
of a full layer, which attends over all the agent remembers, and the bound of
every layer's slots.  A lane's state therefore holds rings of two lengths
side by side (512 and 1,024 slots at the published sizes), a tick writes one
slot of each, and a sequence the learner unrolls grows each window up to its
own span.

The mixer, for H query heads of a kind over G key/value heads of d:
  q = x W_q [H d], k = x W_k, v = x W_v [G d], no bias, no q/k norm; q and k
  turned by position on the kind's rotary dimensions (`rotate_half` form; a
  full layer's cos and sin times YaRN's attention factor); query head i
  reads key/value head i // (H / G); key j is visible to query t iff j <= t,
  t - j < span, same episode; o_i = softmax(q_i K^T / sqrt(d)) V;
  g = sigmoid(x W_g) [H], a gate a head from its own projection of the
  layer's normed input; y = [g_i o_i]_i W_o.
The expert layer: shared(x) + `moe_routed_scaling_factor` x sum over the
chosen 8 of (score / the chosen scores' sum) x expert(x), the scores sigmoids,
the weights on the experts' outputs (`moe_apply_router_weight_on_input`
false), of which this chip adds the terms of the experts it holds.

Per-lane state of a layer, float32, zero = initial: the window's keys,
UN-rotated, and values [B, L, G, d] each, their validity [B, L] and the
ring's head [B]; L = the layer's span on a lane that acts (a RING: a tick
writes its step over the oldest slot and scores one row of `span` slots),
fewer on a sequence the learner unrolls (none at its start, then the steps
written, in position order).  The rotation is applied at use, by a slot's
position among the slots attended over (models/mla_moe.py says why that is
the published rotation by absolute position; it holds for any table of
frequencies).  A call of several steps (burn-in, trained slice, an eval
rollout) attends by blocks of queries, a sliding layer's block over the slots
of its band alone (`mla_moe.attend_by_blocks`): no score array over a whole
1,024-step sequence is ever made.  A ring of `span` slots handed several
steps is first turned into age order, so that a burn-in as long as the span,
whose window has grown to a ring by shape, is attended over like any other
sequence's.  An episode cut inside a sequence is a segment boundary.

Counters, sown a layer and by kind: `attn_live_key_share_sliding` and
`attn_live_key_share_full`, the mask's live entries over T x S (the new steps
times the slots held: whatever the blocks computed, so a block's size cannot
move it); `attn_band_key_share`, a sliding layer's computed columns over
T x S (1.0 would be the dense form); `attn_act_window_written_share` on a
ring.

The plain reference is tests/reference_laguna_core.py.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from flax import linen as nn

from rainbow_iqn_apex_tpu.models.cores import CORE_STATS as STATS
from rainbow_iqn_apex_tpu.models.mla_moe import (
    CoreConfig,
    Rotation,
    StackCore,
    _Linear,
    attend,
    attend_by_blocks,
    ring_in_age_order,
    rotate_table,
    sow_written_share,
    window_mask,
    window_open,
    window_reset,
    window_zero_state,
)
from rainbow_iqn_apex_tpu.obs import device_scopes


@dataclasses.dataclass(frozen=True)
class AttnGeometry:
    """What an attention layer of one kind has of its own."""

    kind: str  # "sliding" or "full": the scope's and the counters' suffix
    heads: int  # query heads
    span: int  # a query sees the last `span` slots; 0: all of `kc.window`
    rotation: Rotation


def yarn_frequencies(dim: int, theta: float, factor: float, original: int,
                     beta_fast: float, beta_slow: float):
    """The `dim` / 2 frequencies of a YaRN-scaled rotation of `dim`
    dimensions, as the published `rope_type` "yarn" is computed: a pair whose
    wavelength fits `beta_fast` times or more into the `original` positions
    keeps its frequency theta^(-2i/dim), one that fits `beta_slow` times or
    fewer has it divided by `factor`, and between the two pair indices
    (rounded outwards) the two are blended by a linear ramp."""
    def pair_of(rotations):  # the pair index that turns so often in `original`
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = [min(max((i - low) / (high - low), 0.0), 1.0)
            for i in range(dim // 2)]
    return tuple(f / factor * r + f * (1.0 - r)
                 for f, r in zip(plain_frequencies(dim, theta), ramp))


def plain_frequencies(dim: int, theta: float):
    """theta^(-2i/dim), i < dim / 2: an unscaled rotation's."""
    return tuple(theta ** (-2.0 * i / dim) for i in range(dim // 2))


def _rotation(rp: Dict[str, Any], head_dim: int) -> Rotation:
    """One kind's entry of the published `rope_parameters` as a table."""
    dim = int(head_dim * rp.get("partial_rotary_factor", 1))
    theta = float(rp["rope_theta"])
    kind = rp.get("rope_type", "default")
    if kind == "default":
        return Rotation(plain_frequencies(dim, theta))
    if kind != "yarn" or rp.get("mscale") or rp.get("mscale_all_dim") or (
            not rp.get("truncate", True)):
        raise ValueError(f"a rotation of rope_type {kind!r} (or a yarn one "
                         f"with mscale or without truncation) is not written")
    factor = rp["factor"]
    attention = rp.get("attention_factor")
    if attention is None:  # the published default of a yarn rotation
        attention = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return Rotation(
        yarn_frequencies(dim, theta, factor,
                         rp["original_max_position_embeddings"],
                         rp.get("beta_fast") or 32, rp.get("beta_slow") or 1),
        float(attention))


def _kind_scope(kind: str):
    """The scope a mixer of `kind` wears round all of its work."""
    if kind == "sliding":
        return jax.named_scope(device_scopes.ATTN_SLIDING)
    return jax.named_scope(device_scopes.ATTN_FULL)


class _GatedGQA(nn.Module):
    """The mixer; `gated_gqa` makes the subclass of a kind, whose `geom` says
    what the kind has of its own."""

    kc: CoreConfig
    compute_dtype: Any

    layer_name = "gqa"
    geom = None

    reset_state = staticmethod(window_reset)

    @classmethod
    def span(cls, kc: CoreConfig) -> int:
        """The layer's span under the agent's memory `kc.window`, which
        bounds every layer's (0: a sequence's start, `from_stored`)."""
        return min(cls.geom.span or kc.window, kc.window)

    @classmethod
    def zero_state(cls, kc: CoreConfig, batch: int):
        kv = (kc.attn_kv_heads, kc.attn_head_dim)
        return window_zero_state(batch, cls.span(kc), k=kv, v=kv)

    @nn.compact
    def __call__(self, x, state, seg):
        kc, cd, geom = self.kc, self.compute_dtype, self.geom
        b, t, _ = x.shape
        h, g, d, w = geom.heads, kc.attn_kv_heads, kc.attn_head_dim, self.span(kc)
        rot = geom.rotation
        ring = state["valid"].shape[1] == w
        with _kind_scope(geom.kind):
            with jax.named_scope(device_scopes.MHA_PROJ):
                q = _Linear(h * d, cd, name="q_proj")(x).reshape(
                    b, t, g, h // g, d)
                k = _Linear(g * d, cd, name="k_proj")(x).reshape(b, t, g, d)
                v = _Linear(g * d, cd, name="v_proj")(x).reshape(b, t, g, d)
                gate = _Linear(h, cd, name="g_proj")(x).reshape(
                    b, t, g, h // g, 1)
            win = window_open(state, {"k": k, "v": v}, seg, w)
            with jax.named_scope(device_scopes.MHA_ATTN):
                if ring and t == 1:  # a tick: one row of scores over the ring
                    with jax.named_scope(device_scopes.MHA_ROPE):
                        q = rotate_table(q, win.pos_q, rot)
                        k_at = rotate_table(win.held["k"], win.pos_k, rot)
                    mask = window_mask(win, seg, w)
                    o = attend(q, k_at, win.held["v"], mask, cd)
                    live, slots = jnp.sum(mask, dtype=jnp.float32), w
                else:  # several steps: by blocks over slots in position order
                    held, valid = (
                        ring_in_age_order(win, state["head"], w) if ring
                        else (win.held, win.valid))
                    slots = valid.shape[1]
                    with jax.named_scope(device_scopes.MHA_ROPE):
                        q = rotate_table(q, slots - t + jnp.arange(t), rot)
                        k_at = rotate_table(held["k"], jnp.arange(slots), rot)
                    # cast once, not a block: what the blocks keep for the
                    # way back is in the products' operand type
                    o, live, computed = attend_by_blocks(
                        q.astype(cd), k_at.astype(cd), held["v"].astype(cd),
                        valid, win.seg, seg, w, cd)
                    if geom.kind == "sliding":
                        self.sow(STATS, "attn_band_key_share",
                                 computed / (t * slots))
                o = o * jax.nn.sigmoid(gate)
            with jax.named_scope(device_scopes.MHA_PROJ):
                y = _Linear(kc.hidden, cd, name="o_proj")(
                    o.reshape(b, t, h * d))
        self.sow(STATS, "attn_live_key_share_" + geom.kind,
                 live / (b * t * slots))
        sow_written_share(self, state, t, w)
        return y, win.state


@functools.lru_cache(maxsize=None)
def gated_gqa(geom: AttnGeometry):
    """The mixer class of one kind of attention layer: one class a geometry,
    so two readings of one file build equal configurations."""
    return type(f"_GatedGQA_{geom.kind}", (_GatedGQA,), {"geom": geom})


_KINDS = {"full_attention": "full", "sliding_attention": "sliding"}


class LagunaConfig(CoreConfig):
    """`CoreConfig` read from a `laguna` configuration file."""

    @classmethod
    def from_dict(cls, cc: Dict[str, Any]) -> "LagunaConfig":
        assumed = cc.get("assumed", {})
        first, n = cc.get("first_layer_here", 0), cc["layers_here"]
        here = slice(first, first + n)
        kinds, ffns = cc["layer_types"][here], cc["mlp_layer_types"][here]
        heads = cc["num_attention_heads_per_layer"][here]
        if len(kinds) != n or set(kinds) - set(_KINDS):
            raise ValueError(
                f"layers {first} to {first + n} of layer_types are {kinds}: "
                f"a layer that is neither full_attention nor "
                f"sliding_attention is not written")
        dense = sum(f == "dense" for f in ffns)
        if list(ffns) != ["dense"] * dense + ["sparse"] * (n - dense):
            raise ValueError(
                f"mlp_layer_types {ffns}: a dense feed-forward after a "
                f"sparse one is not written")
        if cc.get("attention_bias") or cc.get("gating") not in (
                True, "per-head"):
            raise ValueError("a bias on the attention projections and "
                             "attention without a gate a head are not "
                             "written")
        if cc.get("moe_apply_router_weight_on_input"):
            raise ValueError("router weights on the experts' input are not "
                             "written: they weigh the experts' outputs")
        if not cc.get("norm_topk_prob", True):
            raise ValueError("un-normalised expert weights are not written: "
                             "the chosen scores are divided by their sum")
        d, g = cc["head_dim"], cc["num_key_value_heads"]
        if any(h % g for h in heads):
            raise ValueError(f"{heads} query heads over {g} key/value heads")
        window = assumed.get("attn_window", 1024)
        spans = {"full_attention": 0,
                 "sliding_attention": cc["sliding_window"]}
        turns = {k: _rotation(cc["rope_parameters"][k], d) for k in set(kinds)}
        return cls(
            hidden=cc["hidden_size"],
            mixers=tuple(gated_gqa(AttnGeometry(
                _KINDS[k], h, spans[k], turns[k])) for k, h in zip(kinds, heads)),
            eps=cc["rms_norm_eps"], first_dense=dense,
            dense_width=cc["intermediate_size"],
            attn_kv_heads=g, attn_head_dim=d, window=window,
            experts=cc["num_experts"], top_k=cc["num_experts_per_tok"],
            expert_width=cc["moe_intermediate_size"],
            shared_width=cc["shared_expert_intermediate_size"],
            route_scale=cc["moe_routed_scaling_factor"],
            experts_here=cc["experts_here"],
            first_expert=cc.get("first_expert_here", 0),
            in_proj=True,
        )


@dataclasses.dataclass(frozen=True)
class LagunaCore(StackCore):
    kc: CoreConfig
    compute_dtype: Any = jnp.bfloat16

    @property
    def stat_names(self):
        kinds = sorted({m.geom.kind for m in self.kc.mixers})
        names = tuple("attn_live_key_share_" + k for k in kinds)
        if "sliding" in kinds:
            names += ("attn_band_key_share",)
        if self.kc.first_dense < self.kc.layers:
            names = self.moe_stat_names + names + ("moe_row_fill_share",)
        return names
