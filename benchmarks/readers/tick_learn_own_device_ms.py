"""Device self time a learn step of `tick_learn`'s own ops: in `tick_learn`
and in none of `learn_step`, `replay_draw`, `replay_gather`,
`replay_writeback`.  The conditional and the scan over `learn_fn` themselves,
and what the compiler put in their bodies without a name (the relayouts of the
learn step's operands inherit the caller's path, which is `tick_learn` alone):
until PR 37 read only by elimination."""

from benchmarks import idle, scopes

NAMED = {"learn_step", "replay_draw", "replay_gather", "replay_writeback"}


def read(ctx):
    attr = scopes.attribution(ctx)
    steps = ctx.window["traced"]["steps"] if attr is not None else 0
    if not steps:
        return None
    idle.say_largest(ctx, "tick_learn_own", lambda p: "tick_learn" in p
                     and not NAMED & p, n=24)
    own = sum(t for path, t in attr["by_path"].items()
              if "tick_learn" in path.split("/")
              and not NAMED & set(path.split("/")))
    return 1e3 * own / steps
