"""Model FLOPs of one learn step of the R2D2 agent with the Qwen3-Next core
(layers 1 to 4 of Qwen3-Next-80B-A3B), from shapes.  A multiply-add is two;
recomputed operations do not count.

What the algorithm needs on this chip: every matrix product of a token's path
through the layers held here: the input projection; in a Gated DeltaNet layer
the projections (q, k, v, z; b, a; o), the convolution's taps and the
recurrence as written (per step and value head: decay, k^T S, the rank-one
update, S^T q: 8 d_k d_v); in the attention layer the projections (q with its
gate, k, v, o) and the scores and values over the causal half of the sequence
(256 + 256 a query head and key); in every layer the router over all experts,
the shared expert with its gate and the held experts by the share of
assignments an even router sends them, `experts_here / num_experts` (the 32
of 512 this chip holds see 1/16 of the 10 assignments a token makes); and the
trunk and heads of benchmarks/flops.py, the heads on the core's hidden size.
The rotation is six operations a pair and is left out.  The online net runs
forward over burn-in and forward and backward (twice the forward) over the
trained slice, the target net forward over both.
No kernel is written for this core: the in-chunk preparation of the scan is
the Kimi-Linear core's (models/kda_tile.py), the grouped products are
`jax.lax.ragged_dot`, the compiler's own; so there is no roofline function
here.
"""

from __future__ import annotations

from benchmarks import flops


def core_token_flops(cc: dict, seq_len: int, features: int) -> float:
    """Forward FLOPs of one token through the layers held here, at the mean
    attended length of a `seq_len`-step causal sequence; `features` is what
    the trunk feeds the input projection."""
    hid = cc["hidden_size"]
    hv, dk, dv = (cc["linear_num_value_heads"], cc["linear_key_head_dim"],
                  cc["linear_value_head_dim"])
    key_dim, value_dim = cc["linear_num_key_heads"] * dk, hv * dv
    gdn = 2 * (hid * (2 * key_dim + 2 * value_dim) + hid * 2 * hv
               + value_dim * hid)
    gdn += 2 * (2 * key_dim + value_dim) * cc["linear_conv_kernel_dim"]
    gdn += 8 * hv * dk * dv
    heads, kv, d = (cc["num_attention_heads"], cc["num_key_value_heads"],
                    cc["head_dim"])
    attn = 2 * (hid * heads * 2 * d + 2 * hid * kv * d + heads * d * hid)
    attn += 2 * heads * (d + d) * (seq_len + 1) / 2
    held = cc["num_experts_per_tok"] * cc["experts_here"] / cc["num_experts"]
    moe = 2 * hid * cc["num_experts"] + 2 * 3 * hid * (
        cc["moe_intermediate_size"] * held
        + cc["shared_expert_intermediate_size"]) + 2 * hid
    total = 2.0 * features * hid
    for layer in range(1, cc["layers_here"] + 1):
        total += attn if layer % cc["full_attention_interval"] == 0 else gdn
        total += moe
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
