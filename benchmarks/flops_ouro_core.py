"""Model FLOPs of one learn step of the R2D2 agent with the Ouro core (layers
1 to 4 of Ouro-2.6B run `total_ut_steps` = 4 times over the same weights),
from shapes.  A multiply-add is two; recomputed operations do not count.

What the algorithm needs on this chip: the input projection once, and then
every matrix product of a token's path through EVERY application of a layer,
`total_ut_steps x layers_here` = 16 of them: that the weights are shared makes
the parameters a quarter of such a path's, not its work.  An application is
the four attention projections (q, k, v, o), the scores and values over the
causal half of the sequence (d + d a query head and key) and the three
products of the SwiGLU.  The rotation is six operations a pair and is left
out, as are the norms (68 of them a forward, no products).  Then the trunk
and heads of benchmarks/flops.py, the heads on the core's hidden size.  The
online net runs forward over burn-in and forward and backward (twice the
forward) over the trained slice, the target net forward over both.
No kernel is written for this core: every product is the compiler's own; so
there is no roofline function here.
"""

from __future__ import annotations

from benchmarks import flops


def layer_token_flops(cc: dict, seq_len: int) -> float:
    """Forward FLOPs of one token through ONE application of a layer, at the
    mean attended length of a `seq_len`-step causal sequence."""
    hid = cc["hidden_size"]
    heads, kv, d = (cc["num_attention_heads"], cc["num_key_value_heads"],
                    cc["head_dim"])
    attn = 2 * (2 * hid * heads * d + 2 * hid * kv * d)
    attn += 2 * heads * (d + d) * (seq_len + 1) / 2
    return attn + 2 * 3 * hid * cc["intermediate_size"]


def core_token_flops(cc: dict, seq_len: int, features: int) -> float:
    """Forward FLOPs of one token through the core: the input projection and
    every pass of every layer held here; `features` is what the trunk feeds
    the input projection."""
    applications = cc["total_ut_steps"] * cc["layers_here"]
    return (2.0 * features * cc["hidden_size"]
            + applications * layer_token_flops(cc, seq_len))


def learn_flops(fields: dict, cc: dict, frame_shape, actions: int) -> float:
    h, w = frame_shape
    trunk, first, feat = flops.trunk_flops(h, w, fields["history_length"])
    burn, train = fields["r2d2_burn_in"], fields["r2d2_seq_len"]
    body = trunk + core_token_flops(cc, burn + train, feat)
    heads = flops.heads_flops(cc["hidden_size"], fields["hidden_size"], actions)
    online = burn * body + train * (3 * (body + heads) - first)
    target = (burn + train) * body + train * heads
    return float(fields["batch_size"] * (online + target))
