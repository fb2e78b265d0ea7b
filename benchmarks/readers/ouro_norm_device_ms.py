"""Device self time a learn step of the core's norms (`core_norm` inside
`learn_step`): four a block in 16 layer applications and the final norm after
each of the four passes, 68 a forward, with their backward reductions.  What
`core_norm_device_ms` reads in its cells, where a forward has 9 or 11.  None
where the program has no such scope."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(ctx, "steps", "learn_step", "core_norm") or None
