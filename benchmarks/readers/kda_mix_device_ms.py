"""Device self time a learn step of the KDA mixer but its scan (`kda_mix`
inside `learn_step`), on both sides of it: the q, k, v projections, the short
convolutions, the l2 norms, the low-rank decay and gate projections, `o_norm`
and the output projection, forward and backward, in the layers that have one
(the twin of `qwen3next_gdn_mix_device_ms`).  None where the module's text
names no `kda_mix`."""

from benchmarks import idle, scopes


def read(ctx):
    if not idle.named(ctx, "kda_mix"):
        return None
    return scopes.ms_per(ctx, "steps", "learn_step", "kda_mix")
