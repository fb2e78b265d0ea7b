"""Device self time a learn step of the conv trunk (`net_trunk` inside
`learn_step`): the first conv with its input side (`net_stem`), conv 2 and 3,
forward over the online and target nets and backward.  None where the program
has no such scope."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(ctx, "steps", "learn_step", "net_trunk") or None
