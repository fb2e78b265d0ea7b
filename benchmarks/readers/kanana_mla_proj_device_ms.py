"""Device self time a learn step of MLA's projections in the five layers: q,
kv_a with kv_norm, kv_b over [window; new] and o, forward and backward
(`mla_proj` inside `learn_step`).  None where the program has no such scope."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(ctx, "steps", "learn_step", "mla_proj") or None
