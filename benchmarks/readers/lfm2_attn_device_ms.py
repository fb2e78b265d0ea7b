"""Device self time a learn step of the one attention layer: its four
projections with the q/k norms (`mha_proj`) and the rotation by the slot
(`mha_rope`, inside `mha_attn`), scores, mask, softmax and values of 32 query
heads over the 8 key/value heads' window (`mha_attn`), forward and backward,
inside `learn_step`.  None where the program has no such scopes."""

from benchmarks import scopes


def read(ctx):
    parts = [scopes.ms_per(ctx, "steps", "learn_step", scope)
             for scope in ("mha_proj", "mha_attn")]
    return None if None in parts else sum(parts) or None
