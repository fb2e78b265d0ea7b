"""95th percentile of the host-clock spans round one dispatch and its
`ts.step` read-back, over every dispatch of the window."""

import statistics
import sys


def read(ctx):
    ms = [1e3 * (b - a) for a, b in ctx.spans]
    print(f"segment_p95_ms over {len(ms)} dispatches", file=sys.stderr)
    if len(ms) < 2:
        return None
    return statistics.quantiles(ms, n=20, method="inclusive")[18]
