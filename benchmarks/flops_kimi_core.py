"""Model FLOPs of one learn step of the R2D2 agent with the Kimi-Linear core,
from shapes.  A multiply-add is two; recomputed operations do not count.

What the algorithm needs on this chip: every matrix product of a token's
path through the layers held here (the held experts by the share of
assignments an even router sends them, `experts_here / num_experts`: the
8 of 256 this chip holds see 1/32 of the 8 assignments a token makes), the
KDA recurrence as written (per step and head: decay, k^T S, the rank-one
update, S^T q: 8 d_k d_v), the MLA scores and values over the causal
half of the sequence, and the trunk and heads of benchmarks/flops.py.
The online net runs forward over burn-in and forward and backward (twice
the forward) over the trained slice, the target net forward over both.
No kernel is written for this core (the grouped products are
`jax.lax.ragged_dot`, the compiler's own), so there is no roofline function.
"""

from __future__ import annotations

from benchmarks import flops


def core_token_flops(cc: dict, seq_len: int) -> float:
    """Forward FLOPs of one token through the layers held here, at the mean
    attended length of a `seq_len`-step causal sequence."""
    la = cc["linear_attn_config"]
    hid, rank = cc["hidden_size"], cc["assumed"]["low_rank"]
    h, dk = la["num_heads"], la["head_dim"]
    d = h * dk
    kda = 2 * (3 * hid * d + d * hid + 2 * (hid * rank + rank * d) + hid * h)
    kda += 2 * 3 * d * la["short_conv_kernel_size"] + 8 * h * dk * dk
    mh = cc["num_attention_heads"]
    qk, dv = cc["qk_nope_head_dim"] + cc["qk_rope_head_dim"], cc["v_head_dim"]
    kvr, nope = cc["kv_lora_rank"], cc["qk_nope_head_dim"]
    mla = 2 * (hid * mh * qk + hid * (kvr + cc["qk_rope_head_dim"])
               + kvr * mh * (nope + dv) + mh * dv * hid)
    mla += 2 * mh * (qk + dv) * (seq_len + 1) / 2
    dense = 2 * 3 * hid * cc["intermediate_size"]
    ew = cc["moe_intermediate_size"]
    held = cc["num_experts_per_token"] * cc["experts_here"] / cc["num_experts"]
    moe = 2 * hid * cc["num_experts"] + 2 * 3 * hid * ew * (
        cc["num_shared_experts"] + held)
    total = 0.0
    for layer in range(1, cc["layers_here"] + 1):
        total += kda if layer in la["kda_layers"] else mla
        total += dense if layer <= cc["first_k_dense_replace"] else moe
    return total


def learn_flops(fields: dict, cc: dict, frame_shape, actions: int) -> float:
    h, w = frame_shape
    trunk, first, feat = flops.trunk_flops(h, w, fields["history_length"])
    burn, train = fields["r2d2_burn_in"], fields["r2d2_seq_len"]
    body = trunk + core_token_flops(cc, burn + train)
    heads = flops.heads_flops(feat, fields["hidden_size"], actions)
    online = burn * body + train * (3 * (body + heads) - first)
    target = (burn + train) * body + train * heads
    return float(fields["batch_size"] * (online + target))
