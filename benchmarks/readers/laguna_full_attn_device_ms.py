"""Device self time a learn step of the two full-attention layers'
attention proper (`mha_attn` inside `attn_full` inside `learn_step`): the
YaRN-scaled rotation of half of each of 48 query heads and 8 key heads
(`mha_rope`), and a block of 128 queries at a time the scores, mask, softmax
and values over every slot up to the block's end, then the gate's product;
forward, the blocks made again on the way back, and backward.  None where the
program has no such scopes."""

from benchmarks import scopes


def read(ctx):
    return scopes.ms_per(
        ctx, "steps", "learn_step", "attn_full", "mha_attn") or None
