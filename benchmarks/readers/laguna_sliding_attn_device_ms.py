"""Device self time a learn step of the three sliding-window layers'
attention proper (`mha_attn` inside `attn_sliding` inside `learn_step`): the
rotation of 64 query heads and 8 key heads whole by a plain table
(`mha_rope`), and a block of 128 queries at a time the scores, mask, softmax
and values over the slots of the block's band, then the gate's product;
forward, the blocks made again on the way back, and backward.  None where the
program has no such scopes (a program from before this family)."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(
        ctx, "steps", "learn_step", "attn_sliding", "mha_attn") or None
