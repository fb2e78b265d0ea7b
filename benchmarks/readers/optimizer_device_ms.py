"""Device self time a learn step of the optimizer (`optimizer` inside
`learn_step`): `tx.update`, `apply_updates` and the target copy; 20 B a
parameter pass through it.  None where the program has no such scope."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(ctx, "steps", "learn_step", "optimizer") or None
