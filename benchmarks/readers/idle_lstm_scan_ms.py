"""Of `idle_learn_ms`, the part whose closing op is inside the learn step's
LSTM scans (`learn_step` + `lstm_scan`): how much of the dispatch's idle the
loops of small ops of the scans hold, a learn step.  None where no gap ends
on such an op (a core without an LSTM)."""

from benchmarks import idle

SCAN = {"tick_learn", "learn_step", "lstm_scan"}


def read(ctx):
    s = idle.idle_seconds(ctx, lambda path: SCAN <= path)
    if not s:
        return None
    steps = idle.device_time(ctx)["steps"]
    return 1e3 * s / steps if steps else None
