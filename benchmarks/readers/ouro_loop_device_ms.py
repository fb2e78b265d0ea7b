"""Device self time a learn step of the looped stack, all four passes of the
four layers with the final norm after each (`loop_pass` inside `learn_step`),
forward and backward: the 16 layer applications over the same weights, which
are all of the core but its input projection.  None where the program has no
such scope."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(ctx, "steps", "learn_step", "loop_pass") or None
