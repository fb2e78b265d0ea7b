"""Share of the slots held that the sliding-window layers' blocks computed
scores for, in the last learn step the driver saw (the program's own counter
`attn_band_key_share`, the mean over the three sliding layers of the trained
slice's pass, x 100): 4 blocks of 128 queries over 639 slots each of the
1,024 held read 62.4; 100.0 would be the dense form.  None where the driver
keeps no such counter (a program from before it)."""

def read(ctx):
    value = getattr(ctx.driver, "counters", {}).get("attn_band_key_share")
    return None if value is None else 100.0 * value
