"""Device self time a learn step of the dense SwiGLU (`dense_ffn` inside
`learn_step`) in all 16 layer applications, forward and backward: three
products of 2048 x 5632 a token and application, two thirds of the core's
FLOPs.  What `dense_ffn_device_ms` reads in its cells, where one layer in
five is dense and runs once.  None where the program has no such scope."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(ctx, "steps", "learn_step", "dense_ffn") or None
