"""The harness: finds a cell's files by name, drives its driver through
set-up, a measured window and the check of outputs, and prints the result.

Everything that belongs to one cell, configuration, traffic mix, driver or
per-layer metric is a file of its own, found by the name in BENCHMARK.json:

  workloads/<cell>.json   config, traffic, chips, the limits of `correct`
                          (and `read_not_compared`: numbers printed, not judged)
  configs/<config>.json   source, driver, the program's Config fields
  traffic/<traffic>.json  the env and lane fields of the traffic mix
  drivers/<driver>.py     class Driver (builds and drives the program)
  readers/<metric>.py     read(ctx) -> number or None, one per-layer metric

Which metrics a cell reports, and their units, is read from BENCHMARK.json:
an entry of `end_to_end` or `per_layer` that lists `workloads` is for those
cells, one that does not is for every cell.

A later PR adds files and entries and edits none that is here.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, "chiprun_out", "benchmarks")
TRACE_MIN_SEGMENTS, TRACE_MAX_SEGMENTS, TRACE_SECONDS = 2, 8, 2.0


def load_json(kind: str, name: str) -> dict:
    path = os.path.join(HERE, kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def load_cell(name: str):
    wl = load_json("workloads", name)
    cfg = load_json("configs", wl["config"])
    return wl, cfg, load_json("traffic", wl["traffic"])


def metric_specs(cell: str, kind: str):
    """The entries of BENCHMARK.json's `kind` (`end_to_end` or `per_layer`)
    that this cell reports."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        specs = json.load(f)[kind]
    return [m for m in specs if cell in m.get("workloads", [cell])]


def load_reader(metric: str):
    """readers/<metric>.py, by path: a metric's name may hold `.` and `-`."""
    path = os.path.join(HERE, "readers", metric + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + metric, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def device_gate(chips: int):
    """The local TPU devices the cell runs on, or None (no result then)."""
    import jax

    try:
        devs = [d for d in jax.local_devices() if d.platform == "tpu"]
    except RuntimeError:
        devs = []
    if jax.default_backend() != "tpu" or len(devs) < chips:
        print(f"benchmarks: this cell needs {chips} TPU chip(s); JAX found "
              f"backend {jax.default_backend()!r} with {len(devs)}",
              file=sys.stderr)
        return None
    return devs[:chips]


def enable_cache():
    """Persistent compilation cache at a fixed path inside the checkout
    (or where the operator placed it), keeping sub-second compiles too."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class Context:
    """What a reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def measure(drv, seconds: float, trace_dir=None):
    """The measured window: the trainer's loop for `seconds`, closed by the
    read-back of the last dispatch.  With `trace_dir` the profiler runs over
    the window's first few dispatches.  Returns the window's counts."""
    import jax

    seg0, failed, traced = drv.segments, 0, None
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    start = time.perf_counter()
    step0 = step = int(drv.carry[0].step)
    while True:
        with jax.profiler.TraceAnnotation("bench_segment"):
            new_step, outs, _k = drv.dispatch()
        # a dispatch fails if a learn step it owed left no finite loss
        failed += int(np.isfinite(np.asarray(outs[1])).sum() != new_step - step)
        step = new_step
        now, n = drv.spans[-1][1], drv.segments - seg0
        if trace_dir and traced is None and (
                n >= TRACE_MAX_SEGMENTS
                or (n >= TRACE_MIN_SEGMENTS and now - start >= TRACE_SECONDS)):
            jax.profiler.stop_trace()  # slow; the gap it leaves is no host gap
            traced = {"seconds": now - start, "steps": step - step0,
                      "segments": n}
        if now - start >= seconds and not (trace_dir and traced is None):
            break
    return {"seg0": seg0, "segments": drv.segments - seg0,
            "steps": step - step0, "elapsed": drv.spans[-1][1] - start,
            "failed": failed, "traced": traced}


def run(workload: str, seed: int, seconds: float, trace: bool, *, t0: float,
        keep_trace: bool = False, devices=None, make_driver=None,
        out=sys.stdout) -> int:
    """`keep_trace` leaves a traced run's profile under `OUT_DIR` (a look by
    hand; no number depends on it).  `devices` and `make_driver` are for
    tests: given devices skip the look for a chip, a given factory stands in
    for the cell's driver."""
    import jax

    from benchmarks import check

    wl, cfg, traffic = load_cell(workload)
    chips = int(wl["chips"])
    if devices is None:
        devices = device_gate(chips)
        if devices is None:
            return 3
        enable_cache()
    if make_driver is None:
        make_driver = importlib.import_module(
            "benchmarks.drivers." + cfg["driver"]).Driver
    stage = lambda what: print(  # noqa: E731
        f"set-up: {what} at {time.perf_counter() - t0:.2f} s", file=sys.stderr)
    stage("imports done, device found")
    drv = make_driver(cfg["fields"], traffic, seed, chips, stage=stage)
    jax.block_until_ready(drv.carry)
    stage("program built, state made from the seed")
    drv.warm_up()
    jax.block_until_ready(drv.carry)
    setup_s = time.perf_counter() - t0

    trace_dir = None
    if trace:
        trace_dir = os.path.join(OUT_DIR, workload, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
    win = measure(drv, seconds, trace_dir)
    peak = drv.peak_bytes()
    e2e = {
        "setup_s": setup_s,
        "learn_steps_per_s": win["steps"] / win["elapsed"],
        "env_frames_per_s":
            win["segments"] * drv.frames_per_segment / win["elapsed"],
        "peak_hbm_gb": peak / 1e9,
    }
    print(f"window: {win['segments']} segments, {win['steps']} learn steps "
          f"in {win['elapsed']:.4f} s; set-up {setup_s:.2f} s",
          file=sys.stderr)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips, "memory_peak_bytes": peak}
    metrics, breakdown = {}, None
    if trace:
        from benchmarks import trace_reduce

        reduced = trace_reduce.reduce_dir(trace_dir, chips)
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        with open(os.path.join(HERE, "peaks.json")) as f:
            peaks = json.load(f)
        if dev.device_kind not in peaks:
            raise SystemExit(f"no published peaks for device kind "
                             f"{dev.device_kind!r} in benchmarks/peaks.json")
        ctx = Context(driver=drv, spans=list(drv.spans), trace=reduced,
                      window=win, chips=chips, peaks=peaks[dev.device_kind])
        for spec in metric_specs(workload, "per_layer"):
            value = load_reader(spec["name"]).read(ctx)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"][:10],
                     "idle_gaps": reduced["idle_gaps"][:10]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in metric_specs(workload, "end_to_end")}

    # the check of outputs: once the window has closed, the peak has been
    # read and the program's state is freed
    owed = drv.expected_steps(win["seg0"], drv.segments)
    drv.free()
    prog = drv.program_side()
    ref = drv.reference_side(
        None, prog["priority_after"] != drv.priority0())
    numbers = check.compare(prog, ref, drv.params0)
    numbers["window_steps_missing"] = float(abs(owed - win["steps"]))
    numbers["first_steps_missing"] = float(
        abs(len(ref["loss"]) - drv.first_learning["steps"]))
    read_only = wl.get("read_not_compared", ())
    correct, rows = check.verdict(numbers, wl["limits"], read_only)
    correct = correct and win["failed"] == 0
    for name in read_only:
        print(f"read {name} = {numbers[name]:.6g} (not compared)",
              file=sys.stderr)
    for name, value, limit in rows:
        print(f"compared {name} = {value:.6g} (limit {limit:.6g})",
              file=sys.stderr)
    result = {"correct": correct, "attempted": win["segments"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    number = lambda x: float(x) if np.isfinite(x) else None  # noqa: E731
    if read_only:
        result["read_not_compared"] = {n: number(numbers[n]) for n in read_only}
    result["compared"] = {n: {"value": number(v), "limit": number(lim)}
                          for n, v, lim in rows}
    print(json.dumps(result), file=out, flush=True)
    return 0
