"""Device self time a learn step of the LSTM scans of the learn step, forward
and backward (`lstm_scan` inside `learn_step`; the act tick's one-step scan
is not in it)."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(ctx, "steps", "learn_step", "lstm_scan")
