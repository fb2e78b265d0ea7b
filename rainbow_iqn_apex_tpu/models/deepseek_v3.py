"""A DeepSeek-V3-family model as the recurrent core of `R2D2Net` (interface:
models/cores.py): every mixer MLA with decoupled rotary keys, a dense SwiGLU
in the leading layers and `n_routed_experts` sparse experts beside
`n_shared_experts` shared ones (one SwiGLU of their summed width) in the
rest, sigmoid scores, `noaux_tc` selection, normalised and scaled weights.
configs/cores/kanana_2_30b_a3b.json is layers 1 to 5 of Kanana-2-30B-A3B.

The blocks are models/mla_moe.py's, shared with the Kimi-Linear core; this
module is the reader of the family's published keys.  The agent's only
memory across ticks is each layer's window of latents (a ring a tick writes
one slot of; un-rotated rope keys, rotated at use by their slot's age:
models/mla_moe.py says why that is the published rotation).  The trunk's features are not the model's hidden size
and no width is cut, so an input projection stands where a language model has
its embedding.  `n_group` = `topk_group` = 1 is the only grouping read: the
group limit is then vacuous.

The plain reference is tests/reference_deepseek_v3_core.py.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax.numpy as jnp

from rainbow_iqn_apex_tpu.models.mla_moe import _MLA, CoreConfig, StackCore


class DeepSeekV3Config(CoreConfig):
    """`CoreConfig` read from a `deepseek_v3` configuration file."""

    @classmethod
    def from_dict(cls, cc: Dict[str, Any]) -> "DeepSeekV3Config":
        assumed = cc.get("assumed", {})
        if (cc.get("n_group", 1), cc.get("topk_group", 1)) != (1, 1):
            raise ValueError("group-limited routing (n_group > 1) is not "
                             "written: the expert layer ranks all experts")
        if cc.get("q_lora_rank") or cc.get("rope_scaling"):
            raise ValueError("a low-rank query projection and a scaled "
                             "rotation are not written")
        return cls(
            hidden=cc["hidden_size"], mixers=(_MLA,) * cc["layers_here"],
            first_dense=cc["first_k_dense_replace"], eps=cc["rms_norm_eps"],
            mla_heads=cc["num_attention_heads"], nope=cc["qk_nope_head_dim"],
            rope=cc["qk_rope_head_dim"], v_dim=cc["v_head_dim"],
            kv_rank=cc["kv_lora_rank"],
            window=assumed.get("mla_window", 120),
            dense_width=cc["intermediate_size"],
            experts=cc["n_routed_experts"], top_k=cc["num_experts_per_tok"],
            expert_width=cc["moe_intermediate_size"],
            shared_width=cc["moe_intermediate_size"] * cc["n_shared_experts"],
            route_scale=cc["routed_scaling_factor"],
            experts_here=cc["experts_here"],
            first_expert=cc.get("first_expert_here", 0),
            rope_theta=float(cc["rope_theta"]), in_proj=True,
        )


@dataclasses.dataclass(frozen=True)
class DeepSeekV3Core(StackCore):
    kc: CoreConfig
    compute_dtype: Any = jnp.bfloat16

    stat_names = StackCore.moe_stat_names + ("mla_live_key_share",)
