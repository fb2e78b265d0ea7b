"""Device self time a learn step inside a core's layers that no scope inside
`core_layer` names: `learn_step` + `core_layer` and nothing else on the path
(the residual adds, what remat and the compiler file under the layer alone).
What is still unnamed after PR 37; it should stay a small part of the layer.
None where the module's text does not name the scopes of that PR (an older
program would read its norms and mixers here)."""

from benchmarks import idle, scopes

BARE = {"tick_learn", "learn_step", "core_layer"}


def read(ctx):
    if not idle.named(ctx, "core_layer", "core_norm"):
        return None
    steps = ctx.window["traced"]["steps"]
    if not steps:
        return None
    idle.say_largest(ctx, "core_unnamed", lambda p: {
        "learn_step", "core_layer"} <= p <= BARE)
    bare = sum(t for path, t in scopes.attribution(ctx)["by_path"].items()
               if {"learn_step", "core_layer"} <= set(path.split("/")) <= BARE)
    return 1e3 * bare / steps
