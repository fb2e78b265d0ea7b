"""Device self time a learn step of the gated attention layer: its
projections and norms (`gattn_proj`) and the rotation by the slot
(`gattn_rope`, inside `gattn_attn`), scores, mask, softmax, values and the
gate over the K/V window (`gattn_attn`), forward and backward, inside
`learn_step`.  None where the program has no such scopes."""

from benchmarks import scopes


def read(ctx):
    parts = [scopes.ms_per(ctx, "steps", "learn_step", scope)
             for scope in ("gattn_proj", "gattn_attn")]
    return None if None in parts else sum(parts) or None
