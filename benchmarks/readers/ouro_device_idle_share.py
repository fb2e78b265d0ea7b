"""1 minus the union of the innermost device-op intervals over the traced
window, in percent: how far the host and the waits inside a dispatch hold
the chip back in this cell (what `device_idle_share` reads in `r2d2-fused`:
a `model_config` PR may not append a cell to an accepted list, so the cell
has an entry of its own until a `benchmark` PR folds the two)."""


def read(ctx):
    t = ctx.trace
    if not ctx.window.get("traced") or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
