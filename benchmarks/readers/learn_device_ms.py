"""Device self time a learn step of the learn step inside the `cond` inside
the scan (`learn_step`: forward, loss, backward, optimizer, target copy),
where `learn_only_ms` times a program of its own from outside."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(ctx, "steps", "learn_step")
