"""The cell's learn step alone on one batch the live ring drew, without the
draw and without the priority write-back."""

from benchmarks.readers._timing import mean_call_ms


def read(ctx):
    program = ctx.driver.layer_program("learn_only")
    return None if program is None else mean_call_ms(*program)
