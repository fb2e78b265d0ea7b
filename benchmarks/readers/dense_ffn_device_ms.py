"""Device self time a learn step of the dense layer's SwiGLU (`dense_ffn`
inside `learn_step`): the leading layer of the Kimi-Linear and DeepSeek-V3
cores, three products of the model's intermediate size, forward and backward.
None where the module's text names no `dense_ffn`."""

from benchmarks import idle, scopes


def read(ctx):
    if not idle.named(ctx, "dense_ffn"):
        return None
    return scopes.ms_per(ctx, "steps", "learn_step", "dense_ffn")
