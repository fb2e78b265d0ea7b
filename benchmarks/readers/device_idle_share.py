"""1 minus the union of the innermost device-op intervals over the traced
window, per device, averaged over the cell's devices."""


def read(ctx):
    t = ctx.trace
    if not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
