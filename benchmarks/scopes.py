"""Device time by the program's own scope names, for the readers that report
it (`outside_tick_ms`, `act_tick_device_ms`, `replay_sample_device_ms`,
`priority_writeback_device_ms`, `learn_device_ms`, `lstm_scan_device_ms`,
`unattributed_share`).

The program wraps its work in `jax.named_scope`s whose names are constants of
`rainbow_iqn_apex_tpu/obs/device_scopes.py`; the same module maps each
instruction of a compiled module to its scopes and puts a trace's
per-operation self times (`ctx.trace["device_ops"]`: every operation of the
traced dispatches, averaged over the chips) down to them.  This helper only
fetches the module text from the live driver, a load from the compile cache
of the program the window just ran, and keeps the result on `ctx` so that
seven readers pay for it once.  It dispatches nothing.

A program from before the scopes has no such module: `attribution` then
returns None, and so does every reader that asks it.

JAX's persistent compile cache leaves metadata out of its key, so a program
with scopes loads an executable that a program without them cached, and
with it that program's `op_name`s (my chip runs, PR 25).  Where the text
names no scope at all, this program's own module is compiled once more past
the cache, for its text alone: some 45 s on a v5e, in a traced run only.
"""

from __future__ import annotations

import sys
import time


def attribution(ctx):
    """`device_scopes.attribute` of the traced dispatches, or None (untraced
    window, no device operations, or a program without scopes)."""
    if not hasattr(ctx, "scope_attribution"):
        ctx.scope_attribution = _attribute(ctx)
    return ctx.scope_attribution


def _attribute(ctx):
    ops = ctx.trace.get("device_ops") if ctx.window.get("traced") else None
    if not ops:
        return None
    try:
        from rainbow_iqn_apex_tpu.obs import device_scopes
    except ImportError:
        return None
    drv, t0 = ctx.driver, time.perf_counter()
    text = drv.segment.lower(drv.carry, drv.key).compile().as_text()
    inst = device_scopes.instruction_scopes(text)
    if not any(inst.values()):
        print("scopes: the executable's text names no scope (loaded from a "
              "compile cache that a program without scopes wrote); compiling "
              "this program's module past the cache", file=sys.stderr)
        inst = device_scopes.instruction_scopes(compile_past_cache(drv))
    attr = device_scopes.attribute(ops, inst)
    print(f"scopes: module text of {len(inst)} instructions "
          f"({sum(1 for p in inst.values() if p)} in a scope) read in "
          f"{time.perf_counter() - t0:.2f} s; of {attr['total_s']:.6f} s of "
          f"device self time {attr['tick_s']:.6f} in a tick, "
          f"{attr['outside_tick_s']:.6f} outside, "
          f"{attr['unresolved_s']:.6f} unresolved; the ops' own sum "
          f"{sum(t for _n, t in ops):.6f}; outside: "
          f"{[(n, round(t, 6)) for n, t in attr['outside'][:6]]}; "
          f"unresolved: {[(n, round(t, 6)) for n, t in attr['unresolved'][:6]]}",
          file=sys.stderr)
    return attr


def compile_past_cache(drv) -> str:
    """The text of the driver's segment compiled anew, with the persistent
    cache off for the one compile; nothing is dispatched or written."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    raw = drv.segment.__wrapped__

    def segment(carry, key):  # a new function: jit keeps what it has traced
        return raw(carry, key)

    try:
        fresh = jax.jit(segment, donate_argnums=(0,))
        return fresh.lower(drv.carry, drv.key).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def ms_per(ctx, count_key: str, *scopes: str, every: int = 1):
    """Milliseconds of device self time, inside a tick, of the ops whose path
    names all of `scopes`, per traced dispatch (`count_key` "segments") or
    learn step ("steps"), over `every` (ticks a dispatch, say)."""
    attr = attribution(ctx)
    if attr is None:
        return None
    from rainbow_iqn_apex_tpu.obs import device_scopes

    count = ctx.window["traced"][count_key] * every
    if not count:
        return None
    return 1e3 * device_scopes.seconds(attr, *scopes) / count
