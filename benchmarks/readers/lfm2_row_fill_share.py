"""Share of the taken row buffer's rows that hold an assignment, in the last
learn step the driver saw (the program's own counter `moe_row_fill_share`,
the mean over the four expert layers, x 100): 7,680 held assignments in the
15,360-row buffer read 50.0; the other half of the gather, of the grouped
products' rows and of the scatter is masked.  None where the driver keeps no
such counter (a program from before it)."""


def read(ctx):
    value = getattr(ctx.driver, "counters", {}).get("moe_row_fill_share")
    return None if value is None else 100.0 * value
