"""Model FLOPs of one learn step (benchmarks/flops.py) times the learn steps
per second of the traced window, over chips times the bf16 peak.  An
end-to-end utilization, not a kernel's roofline share: acting, the env and
the ring's work are in the time and not in the FLOPs."""


def read(ctx):
    traced = ctx.window["traced"]
    if not traced or not traced["steps"]:
        return None
    rate = traced["steps"] / traced["seconds"]
    flops = ctx.driver.learn_flops()
    return 100.0 * flops * rate / (ctx.chips * ctx.peaks["bf16_flops_per_s"])
