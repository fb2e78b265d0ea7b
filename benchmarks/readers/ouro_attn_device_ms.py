"""Device self time a learn step of the attention itself over the 16 (pass,
layer) K/V windows: every q and k head turned whole by its slot (`mha_rope`,
inside `mha_attn`), scores, mask, softmax and values (`mha_attn` inside
`learn_step`), forward and backward.  None where the program has no such
scope."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(ctx, "steps", "learn_step", "mha_attn") or None
