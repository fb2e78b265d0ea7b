"""Share of the attention's score columns that the mask leaves, in the last
learn step the driver saw (the program's own counter `attn_live_key_share`,
the mean over the 16 (pass, layer) uses, x 100): 40.25 on the 80-step trained
slice that follows a 40-step burn-in from the empty window over 200 slots.
What is not live is scored and masked all the same.  None where the driver
keeps no such counter."""


def read(ctx):
    value = getattr(ctx.driver, "counters", {}).get("attn_live_key_share")
    return None if value is None else 100.0 * value
