"""Model FLOPs of one learn step of the R2D2 agent with the Laguna core
(layers 0 to 4 of Laguna-XS.2, 16 of each expert layer's 256 experts held),
from shapes.  A multiply-add is two; recomputed operations do not count.

What the algorithm needs on this chip: every matrix product of a token's path
through the layers held here: the input projection; in an attention layer the
five projections (q and o of the layer's own heads, 48 full or 64 sliding, k
and v of the 8 key/value heads, the gate's hidden -> heads) and the scores and
values (d + d a query head and key) over THE KEYS THE QUERY MAY SEE: a full
layer's query at position p of a sequence from the empty state sees p + 1
keys, a sliding layer's min(p + 1, sliding_window); the dense SwiGLU where
`mlp_layer_types` says dense; in the others the router over all experts, the
shared expert, and the held experts by the share of assignments dealt to
them, `experts_here / num_experts` of the `num_experts_per_tok` a token makes
(0.5 a token and layer: what the benchmark's seeded selection bias deals on
every seed, and what an even router would).  Then the trunk and heads of
benchmarks/flops.py, the heads on the core's hidden size.  The rotations, the
norms and the gate's elementwise product are left out.  The online net runs
forward over burn-in and forward and backward (twice the forward) over the
trained slice, the target net forward over both.

The count goes by the mask and not by the program's blocks: a block of 128
queries computes the 639 slots of its band where 512 a query are live, and a
full layer's block every slot up to its end; that is the program's cost, not
the algorithm's, so the size of a block cannot move `laguna_learn_mfu`.
No kernel is written for this core (the attention by blocks is plain
`jax.numpy` under `jax.checkpoint`, the grouped products `jax.lax.ragged_dot`,
the compiler's own), so there is no roofline function.
"""

from __future__ import annotations

from benchmarks import flops


def keys_seen(span: int, lo: int, hi: int) -> int:
    """The keys the queries at positions [lo, hi) of a sequence from the
    empty state may see together: min(p + 1, span) each."""
    return sum(min(p + 1, span) for p in range(lo, hi))


def core_flops(cc: dict, lo: int, hi: int, features: int) -> float:
    """Forward FLOPs of the tokens at positions [lo, hi) of one sequence
    through the layers held here; `features` is what the trunk feeds the
    input projection, and the full layers' span is the sequence."""
    hid, kv, d = cc["hidden_size"], cc["num_key_value_heads"], cc["head_dim"]
    held = cc["num_experts_per_tok"] * cc["experts_here"] / cc["num_experts"]
    moe = 2 * hid * cc["num_experts"] + 2 * 3 * hid * (
        cc["shared_expert_intermediate_size"]
        + cc["moe_intermediate_size"] * held)
    dense = 2 * 3 * hid * cc["intermediate_size"]
    first, tokens = cc.get("first_layer_here", 0), hi - lo
    total = tokens * 2.0 * features * hid
    for layer in range(first, first + cc["layers_here"]):
        heads = cc["num_attention_heads_per_layer"][layer]
        span = (cc["sliding_window"]
                if cc["layer_types"][layer] == "sliding_attention" else hi)
        total += tokens * 2 * (2 * hid * heads * d + 2 * hid * kv * d
                               + hid * heads)
        total += 2 * heads * (d + d) * keys_seen(span, lo, hi)
        total += tokens * (
            dense if cc["mlp_layer_types"][layer] == "dense" else moe)
    return total


def learn_flops(fields: dict, cc: dict, frame_shape, actions: int) -> float:
    h, w = frame_shape
    trunk, first, feat = flops.trunk_flops(h, w, fields["history_length"])
    burn, train = fields["r2d2_burn_in"], fields["r2d2_seq_len"]
    heads = flops.heads_flops(cc["hidden_size"], fields["hidden_size"], actions)
    burned = burn * trunk + core_flops(cc, 0, burn, feat)
    trained = train * trunk + core_flops(cc, burn, burn + train, feat)
    online = burned + 3 * (trained + train * heads) - train * first
    target = burned + trained + train * heads
    return float(fields["batch_size"] * (online + target))
