"""Device self time a learn step of the attention's four projections (q, k,
v, o: `mha_proj` inside `learn_step`), in all 16 layer applications, forward
and backward.  None where the program has no such scope."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(ctx, "steps", "learn_step", "mha_proj") or None
