"""Share of the trained slice's queries x slots held that the sliding-window
layers' mask lets through, in the last learn step the driver saw (the
program's own counter `attn_live_key_share_sliding`, the mean over the three
sliding layers, x 100): 512 queries that each see the last 512 of 1,024 slots
read 50.0 on whole rows, less where a drawn row was cut short.  None where
the driver keeps no such counter."""

def read(ctx):
    value = getattr(ctx.driver, "counters", {}).get(
        "attn_live_key_share_sliding")
    return None if value is None else 100.0 * value
