"""Median host time between one read-back and the next dispatch, without
the one gap in which a traced run stops the profiler."""

import statistics


def read(ctx):
    traced = ctx.window["traced"]
    skip = traced["segments"] - 1 if traced else None  # the profiler's stop
    gaps = [1e3 * (ctx.spans[i + 1][0] - ctx.spans[i][1])
            for i in range(len(ctx.spans) - 1) if i != skip]
    return statistics.median(gaps) if gaps else None
