"""Device self time a dispatch of the instructions the compiler made: those
with no `op_name` metadata of their own in the module's text (layout copies,
the slices and copies of its async pairs, the pieces of loops it unrolled),
wherever their inherited scope files them.  The run's stderr lists them by
the scope path of the op that reads each (`benchmarks/idle.made`).  None on a
program whose `obs/device_scopes.py` does not tell them apart."""

from benchmarks import idle


def read(ctx):
    attr = idle.made(ctx)
    if attr is None:
        return None
    return 1e3 * attr["compiler_made_s"] / ctx.window["traced"]["segments"]
