"""Device self time a learn step of the priority scatter
(`replay_writeback`), which `learn_only_ms` leaves out."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(ctx, "steps", "replay_writeback")
