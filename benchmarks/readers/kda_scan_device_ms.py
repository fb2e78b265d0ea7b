"""Device self time a learn step of the chunked KDA recurrence, forward and
backward (`kda_scan` inside `learn_step`; the act tick's one step is
`core_step`).  None where the program has no such scope."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(ctx, "steps", "learn_step", "kda_scan") or None
