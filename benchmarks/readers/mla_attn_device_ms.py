"""Device self time a learn step of the MLA layer's attention over the
latent window: scores, mask, softmax, values, forward and backward
(`mla_attn` inside `learn_step`).  None where the program has no such scope."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(ctx, "steps", "learn_step", "mla_attn") or None
