"""Model FLOPs of one learn step of the R2D2 agent with the DeepSeek-V3-family
core (layers 1 to 5 of Kanana-2-30B-A3B), from shapes.  A multiply-add is two;
recomputed operations do not count.

What the algorithm needs on this chip: every matrix product of a token's path
through the layers held here: the input projection; in each layer MLA's four
projections and its scores and values over the causal half of the sequence
(192 and 128 a head and key); the dense SwiGLU in the leading layers; in the
others the router over all experts, the shared experts (one SwiGLU of their
summed width) and the held experts by the share of assignments an even
router sends them, `experts_here / n_routed_experts` (the 16 of 128 this chip
holds see 1/8 of the 6 assignments a token makes); and the trunk and heads of
benchmarks/flops.py, the heads on the core's hidden size.  The rotation is
six operations a pair and is left out.  The online net runs forward over
burn-in and forward and backward (twice the forward) over the trained slice,
the target net forward over both.
No kernel is written for this core (the grouped products are
`jax.lax.ragged_dot`, the compiler's own), so there is no roofline function.
"""

from __future__ import annotations

from benchmarks import flops


def core_token_flops(cc: dict, seq_len: int, features: int) -> float:
    """Forward FLOPs of one token through the layers held here, at the mean
    attended length of a `seq_len`-step causal sequence; `features` is what
    the trunk feeds the input projection."""
    hid, heads = cc["hidden_size"], cc["num_attention_heads"]
    nope, rope = cc["qk_nope_head_dim"], cc["qk_rope_head_dim"]
    dv, rank = cc["v_head_dim"], cc["kv_lora_rank"]
    mla = 2 * (hid * heads * (nope + rope) + hid * (rank + rope)
               + rank * heads * (nope + dv) + heads * dv * hid)
    mla += 2 * heads * (nope + rope + dv) * (seq_len + 1) / 2
    dense = 2 * 3 * hid * cc["intermediate_size"]
    held = cc["num_experts_per_tok"] * cc["experts_here"] / cc["n_routed_experts"]
    moe = 2 * hid * cc["n_routed_experts"] + 2 * 3 * hid * cc[
        "moe_intermediate_size"] * (cc["n_shared_experts"] + held)
    total = 2.0 * features * hid
    for layer in range(1, cc["layers_here"] + 1):
        total += mla + (dense if layer <= cc["first_k_dense_replace"] else moe)
    return total


def learn_flops(fields: dict, cc: dict, frame_shape, actions: int) -> float:
    h, w = frame_shape
    trunk, first, feat = flops.trunk_flops(h, w, fields["history_length"])
    burn, train = fields["r2d2_burn_in"], fields["r2d2_seq_len"]
    body = trunk + core_token_flops(cc, burn + train, feat)
    heads = flops.heads_flops(cc["hidden_size"], fields["hidden_size"], actions)
    online = burn * body + train * (3 * (body + heads) - first)
    target = (burn + train) * body + train * heads
    return float(fields["batch_size"] * (online + target))
