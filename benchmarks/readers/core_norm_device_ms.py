"""Device self time a learn step of a core's pre-norms (`core_norm` inside
`learn_step`): every layer's `mix_norm` and `ffn_norm` and the stack's
`final_norm`, forward and backward (the RMSNorm-backward reductions over
`f32[64,80,hidden]`).  None where the module's text names no `core_norm`."""

from benchmarks import idle, scopes


def read(ctx):
    if not idle.named(ctx, "core_norm"):
        return None
    return scopes.ms_per(ctx, "steps", "learn_step", "core_norm")
