"""Model FLOPs of one learn step of the R2D2 agent with the LFM2 core (layers
2 to 6 of LFM2-8B-A1B, 8 of each expert layer's 32 experts held), from shapes.
A multiply-add is two; recomputed operations do not count.

What the algorithm needs on this chip: every matrix product of a token's path
through the layers held here: the input projection; in a `conv` layer the
gated short convolution's two projections (hidden -> 3 hidden, hidden ->
hidden) and its `conv_L_cache` taps a channel; in a `full_attention` layer the
four projections (32 query heads over 8 key/value heads) and the scores and
values over the causal half of the sequence (d + d a query head and key); the
dense SwiGLU in the layers under `num_dense_layers`; in the others the router
over all experts and the held experts by the share of assignments dealt to
them, `experts_here / num_experts` of the `num_experts_per_tok` a token makes
(the 8 of 32 this chip holds see 1 of a token's 4: what the benchmark's
seeded selection bias deals on every seed, and what an even router would);
there is no shared expert.  Then the trunk and heads of benchmarks/flops.py,
the heads on the core's hidden size.  The rotation, the norms (the q/k norms
among them) and the two gates' elementwise products are left out.  The online
net runs forward over burn-in and forward and backward (twice the forward)
over the trained slice, the target net forward over both.
No kernel is written for this core (the grouped products are
`jax.lax.ragged_dot`, the compiler's own), so there is no roofline function.
"""

from __future__ import annotations

from benchmarks import flops


def core_token_flops(cc: dict, seq_len: int, features: int) -> float:
    """Forward FLOPs of one token through the layers held here, at the mean
    attended length of a `seq_len`-step causal sequence; `features` is what
    the trunk feeds the input projection."""
    hid, heads, kv = (cc["hidden_size"], cc["num_attention_heads"],
                      cc["num_key_value_heads"])
    d = cc.get("head_dim") or hid // heads
    conv = 2 * (hid * 3 * hid + hid * hid + cc["conv_L_cache"] * hid)
    attn = 2 * (2 * hid * heads * d + 2 * hid * kv * d)
    attn += 2 * heads * (d + d) * (seq_len + 1) / 2
    dense = 2 * 3 * hid * cc["intermediate_size"]
    held = cc["num_experts_per_tok"] * cc["experts_here"] / cc["num_experts"]
    moe = 2 * hid * cc["num_experts"] + 2 * 3 * hid * cc[
        "moe_intermediate_size"] * held
    first = cc.get("first_layer_here", 0)
    total = 2.0 * features * hid
    for layer in range(first, first + cc["layers_here"]):
        total += conv if cc["layer_types"][layer] == "conv" else attn
        total += dense if layer < cc["num_dense_layers"] else moe
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
