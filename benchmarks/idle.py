"""The other half of the device-time account, for the readers that close it:
idle inside a dispatch by scope path (`idle_learn_ms`, `idle_act_ms`,
`idle_outside_ms`, `idle_lstm_scan_ms`), the instructions the compiler made
(`compiler_made_device_ms`), and the scopes that arrived after the accepted
readers (`core_norm_device_ms`, `dense_ffn_device_ms`, `kda_mix_device_ms`,
`core_unnamed_device_ms`).

`scopes.py` gives device self time by scope from the harness's own trace.
Idle time needs the ops' timeline, which the harness deletes before a reader
runs and `trace_reduce.py` does not keep.  So `device_time(ctx)` takes a
second, short capture once the window has closed, THROUGH THE PROGRAM'S OWN
`obs.trace.TraceWindow`: two dispatches of the driver's segment under
`Tracer.span("segment")`, the segment's text registered with `add_program`,
and the payload of the `device_time` row it logs, which is what an operator
gets from `--trace-dir`.  It leaves the harness's later steps as they were:
it calls the segment itself (not `drv.dispatch`), hands the donated carry
back to the driver, and touches neither `drv.segments`, `drv.spans` nor
`drv.key`, from which `expected_steps` and `correct` are computed after the
readers.  Every reader that asks pays for one capture (kept on `ctx`).

A program from before this reduction (no `instruction_origins` in
`obs/device_scopes.py`, no `idle_ms_by_path_per_step` in the row) makes
every function here return None, and nothing is dispatched.

JAX's persistent compile cache leaves metadata out of its key (`scopes.py`
says more): a segment whose scopes were added after its executable was
cached loads the old names.  `named` compiles the module once more past the
cache where a scope the program has is missing from the text, for its text
alone; where it is still missing the reader says so and returns None.  The
readers that need a scope of PR 37 stand before the others in
`BENCHMARK.json`, so the capture and `made` read the text they settled.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import sys
import time

from benchmarks import scopes

TICK_SCOPES = ("tick_act", "tick_env", "tick_append", "tick_learn")
CAPTURE_DISPATCHES = 2


def program():
    """The program's `obs.device_scopes`, where it can close the account."""
    try:
        from rainbow_iqn_apex_tpu.obs import device_scopes
    except ImportError:
        return None
    return device_scopes if hasattr(
        device_scopes, "instruction_origins") else None


def module_text(ctx) -> str:
    """The compiled text of the driver's segment: a load from the compile
    cache of the program the window ran.  Read once."""
    if not hasattr(ctx, "segment_text"):
        drv, t0 = ctx.driver, time.perf_counter()
        ctx.segment_text = drv.segment.lower(
            drv.carry, drv.key).compile().as_text()
        print(f"idle: module text of {len(ctx.segment_text)} bytes read in "
              f"{time.perf_counter() - t0:.2f} s", file=sys.stderr)
    return ctx.segment_text


def _text_scopes(ctx, ds) -> set:
    if not hasattr(ctx, "segment_scopes"):
        ctx.segment_scopes = set().union(
            *ds.instruction_scopes(module_text(ctx)).values())
    return ctx.segment_scopes


def named(ctx, *needed: str) -> bool:
    """Whether the segment's text names every scope of `needed`, so that
    `scopes.ms_per` can read them.  A program that has no such scope: no,
    quietly.  A program that has it and a text that does not (an executable
    cached before the scope): the module is compiled past the cache, once,
    and the attribution of `scopes.py` made again from that text (same
    instructions, so every accepted scope reads what it read)."""
    ds = program()
    if ds is None or scopes.attribution(ctx) is None:
        return False
    if not set(needed) <= set(ds.ALL_SCOPES):
        return False
    missing = set(needed) - _text_scopes(ctx, ds)
    if missing and not getattr(ctx, "segment_text_fresh", False):
        print(f"idle: the executable's text names no {sorted(missing)} "
              f"(loaded from a compile cache written before those scopes); "
              f"compiling this program's module past the cache",
              file=sys.stderr)
        t0 = time.perf_counter()
        ctx.segment_text = scopes.compile_past_cache(ctx.driver)
        ctx.segment_text_fresh = True
        print(f"idle: compiled past the cache in "
              f"{time.perf_counter() - t0:.2f} s", file=sys.stderr)
        del ctx.segment_scopes
        ctx.scope_attribution = ds.attribute(
            ctx.trace["device_ops"], ds.instruction_scopes(ctx.segment_text))
        missing = set(needed) - _text_scopes(ctx, ds)
    if missing:
        print(f"idle: no instruction of the module's text is in "
              f"{sorted(missing)}: nothing to read", file=sys.stderr)
    return not missing


def made(ctx):
    """`device_scopes.attribute` of the traced dispatches with the
    instructions' origins: the accepted attribution's classes, and beside
    them `compiler_made_s`, `compiler_made_by_consumer_path`,
    `compiler_made`.  None on an untraced window or an older program."""
    if not hasattr(ctx, "made_attribution"):
        ds = program()
        ops = ctx.trace.get("device_ops") if ctx.window.get("traced") else None
        ctx.made_attribution = None
        if ds is not None and ops:
            text = module_text(ctx)
            attr = ds.attribute(ops, ds.instruction_scopes(text),
                                ds.instruction_origins(text))
            ctx.made_attribution = attr
            largest = [(i, round(t, 6), op, sh[:48])
                       for i, t, op, sh, _c in attr["compiler_made"][:8]]
            print(f"idle: {attr['compiler_made_s']:.6f} s of "
                  f"{attr['total_s']:.6f} s of device self time on "
                  f"instructions the compiler made; by consumer: "
                  f"{_top(attr['compiler_made_by_consumer_path'])}; largest: "
                  f"{largest}", file=sys.stderr)
    return ctx.made_attribution


def say_largest(ctx, what: str, want, n: int = 8) -> None:
    """To stderr: the `n` largest traced ops whose scope path `want(set of
    its scopes)` accepts, with opcode, shape and whether the program wrote
    them: what a reader's number is made of."""
    ds = program()
    if ds is None or getattr(ctx, "said_" + what, False):
        return
    setattr(ctx, "said_" + what, True)
    text = module_text(ctx)
    inst, origins = ds.instruction_scopes(text), ds.instruction_origins(text)
    rows = []
    for name, t in ctx.trace["device_ops"]:
        i = ds.instruction_name(name)
        if i in inst and want(set(inst[i])):
            rows.append((i, round(t, 6), origins[i].opcode,
                         origins[i].shape[:48], origins[i].own))
    total = sum(r[1] for r in rows)
    print(f"idle: {what}: {len(rows)} ops, {total:.6f} s; largest "
          f"(instruction, s, opcode, shape, has op_name): "
          f"{sorted(rows, key=lambda r: -r[1])[:n]}", file=sys.stderr)


def _top(d: dict, n: int = 8):
    return [(k, round(v, 6)) for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:n]]


class _Rows:
    """Stands where a `MetricsLogger` would: keeps the rows."""

    def __init__(self):
        self.rows = []

    def log(self, kind, **row):
        self.rows.append((kind, row))


def device_time(ctx):
    """The payload of the `device_time` row of a second capture of
    `CAPTURE_DISPATCHES` dispatches (the module docstring says how and why),
    with `ticks` a dispatch beside it; None where the window was not traced
    or the program's row has no idle by path."""
    if not hasattr(ctx, "idle_device_time"):
        ctx.idle_device_time = _capture(ctx) if (
            ctx.window.get("traced") and program() is not None) else None
    return ctx.idle_device_time


def _capture(ctx):
    import jax

    from benchmarks import harness
    from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry
    from rainbow_iqn_apex_tpu.obs.trace import Tracer, TraceWindow

    drv, t0 = ctx.driver, time.perf_counter()
    text = module_text(ctx)
    logdir = os.path.join(harness.OUT_DIR, f"idle_capture_{os.getpid()}")
    shutil.rmtree(logdir, ignore_errors=True)
    rows, tracer = _Rows(), Tracer(MetricRegistry())
    window = TraceWindow(logdir, 0, 1 << 30, logger=rows, tracer=tracer)
    window.add_program(lambda: text)
    # the keys are made before the capture opens: no program but the segment
    ks = [jax.random.fold_in(drv.key, 0x1D1E + i)
          for i in range(CAPTURE_DISPATCHES)]
    jax.block_until_ready((ks, drv.carry))
    step = step0 = int(drv.carry[0].step)
    t1 = time.perf_counter()
    try:
        window.step(step0)  # opens the capture
        for k in ks:
            with tracer.span("segment"):
                drv.carry, _outs = drv.segment(drv.carry, k)
                step = int(drv.carry[0].step)  # the trainer's sync point
        t2 = time.perf_counter()
        window.close(step)  # stops the profiler, reduces, logs the row
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    payload = next((row for kind, row in rows.rows if kind == "device_time"),
                   None)
    if not payload or "idle_ms_by_path_per_step" not in payload:
        print(f"idle: the second capture gave no idle by path: "
              f"{(payload or {}).get('error', 'no device_time row')}",
              file=sys.stderr)
        return None
    payload["ticks"] = drv.ticks
    idle = payload["window_s"] - payload["busy_s"]
    by_path = idle_seconds_of(payload, lambda path: True)
    print(f"idle: second capture of {payload['dispatches']} dispatches and "
          f"{payload['steps']} learn steps: window {payload['window_s']:.6f} "
          f"s, busy {payload['busy_s']:.6f}, idle {idle:.6f} = by path "
          f"{by_path:.6f} + between dispatches "
          f"{payload['idle_between_dispatches_s']:.6f} (off by "
          f"{idle - by_path - payload['idle_between_dispatches_s']:.2e}); "
          f"set up {t1 - t0:.2f} s, dispatches {t2 - t1:.2f} s, stop and "
          f"reduce {time.perf_counter() - t2:.2f} s; largest: "
          f"{_top(payload['idle_ms_by_path_per_step'])} ms a learn step; "
          f"longest gaps: {payload['idle_gaps'][:6]}", file=sys.stderr)
    print("idle: device_time " + json.dumps(payload), file=sys.stderr)
    for gap in [g for g in payload["idle_gaps"] if "op" in g][:6]:
        print(f"idle: {gap['ms']} ms after {_line(text, gap['after'])} "
              f"before {_line(text, gap['op'])}", file=sys.stderr)
    return payload


def _line(text: str, inst: str) -> str:
    """What the module's text says of `inst`: its line without the shapes of
    a long operand list and without the backend's configuration."""
    m = re.search(rf"^\s*(?:ROOT\s+)?%?{re.escape(inst)} = (.*)$", text, re.M)
    if not m:
        return inst
    line = m.group(1).split(", backend_config=")[0]
    return f"{inst} = {line[:120]} ... {line[-200:]}" if len(
        line) > 330 else f"{inst} = {line}"


def idle_seconds(ctx, want):
    """Seconds of idle inside a dispatch over the second capture whose
    closing op's path `want(set of the path's scopes)` accepts; None where
    there is no such capture."""
    row = device_time(ctx)
    return None if row is None else idle_seconds_of(row, want)


def idle_seconds_of(row, want):
    # the row divides by max(steps, 1): undone the same way
    return sum(ms for path, ms in row["idle_ms_by_path_per_step"].items()
               if want(set(path.split("/")))) * max(row["steps"], 1) / 1e3
