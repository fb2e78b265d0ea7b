"""Device self time a learn step of the gated short convolution in the four
layers that have one (`sconv_mix` inside `learn_step`): the input product
2048 -> 6144, the two gates, the 3-tap depthwise convolution and the output
product, forward and backward.  None where the program has no such scope (a
program from before the LFM2 core)."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(ctx, "steps", "learn_step", "sconv_mix") or None
