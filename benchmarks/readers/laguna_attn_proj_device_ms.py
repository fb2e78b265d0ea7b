"""Device self time a learn step of the five attention layers' projections
(`mha_proj` inside `learn_step`): q and o of 48 or 64 heads of 128, k and v of
8, and the gate's 2048 -> heads, forward and backward: 56% of a token's
forward multiply-adds (benchmarks/flops_laguna_core.py).  What
`ouro_attn_proj_device_ms` reads in its cell.  None where the program has no
such scope."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(ctx, "steps", "learn_step", "mha_proj") or None
