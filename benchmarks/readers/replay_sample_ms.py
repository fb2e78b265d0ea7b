"""The ring's draw and assembly alone, on the live ring at the cell's batch,
as the fused learn tick calls them.  Times the layer from outside."""

from benchmarks.readers._timing import mean_call_ms


def read(ctx):
    program = ctx.driver.layer_program("replay_sample")
    return None if program is None else mean_call_ms(*program)
