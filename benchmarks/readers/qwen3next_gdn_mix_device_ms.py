"""Device self time a learn step of the Gated DeltaNet mixer but its scan, in
the three layers that have one: the q, k, v, z and b, a projections, the one
convolution over [q | k | v], the l2 norms and the repeat of the key heads to
the value heads, the gates, the gated norm and the output projection, forward
and backward (`gdn_mix` inside `learn_step`).  None where the program has no
such scope."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(ctx, "steps", "learn_step", "gdn_mix") or None
