"""Span-based tracing that lines host timing up with XLA traces.

``with tracer.span("learn_step"):`` does three things at once:
  * aggregates the region's wall time into a registry histogram
    (``span_<name>_ms``) — the source of the periodic 'timing' row and the
    /metrics summary;
  * emits ONE exemplar 'span' JSONL row per span name per flush interval,
    carrying span_id/parent_id from a thread-local stack — enough to
    reconstruct the nesting without a row per invocation (a learn loop runs
    thousands of spans per second; exemplars keep the JSONL bounded);
  * wraps ``jax.profiler.TraceAnnotation`` so when a --trace-dir capture is
    armed, the host span shows up as a named region in the XLA trace viewer
    aligned with the device timeline.

Also here: the jax-side gauges (compile and cache-hit counts via
jax.monitoring, device memory via Device.memory_stats) and TraceWindow — the
step-windowed profiler capture of --trace-dir, which reduces its own capture
to one 'device_time' row when it closes (obs/device_scopes.py): device busy
and idle share, milliseconds a learn step by the program's scope names, idle
inside a dispatch by the scope path of the op that ends each gap, the
instructions the compiler made, and the long idle gaps by the host span that
covers them.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
import weakref
from typing import Any, Callable, Dict, Optional

import jax

from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry

_span_ids = itertools.count(1)
_tls = threading.local()


def _stack():
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


class Tracer:
    """Per-run span recorder (see module docstring).  ``logger`` is a
    MetricsLogger (or None: aggregate-only); ``reset_exemplars()`` re-arms
    one exemplar row per span name and is called by RunObs at each periodic
    flush."""

    def __init__(self, registry: MetricRegistry, logger=None, role: str = ""):
        self.registry = registry
        self.logger = logger
        self.role = role
        self._seen: set = set()
        self._seen_lock = threading.Lock()
        self.names: set = set()  # every span name this run has opened

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any):
        sid = next(_span_ids)
        self.names.add(name)
        stack = _stack()
        parent = stack[-1] if stack else 0
        stack.append(sid)
        try:
            annotation = jax.profiler.TraceAnnotation(name)
        except Exception:  # pragma: no cover - profiler backend quirks
            annotation = contextlib.nullcontext()
        t0 = time.perf_counter()
        try:
            with annotation:
                yield
        finally:
            dur_ms = (time.perf_counter() - t0) * 1e3
            stack.pop()
            self.registry.histogram(f"span_{name}_ms", self.role).observe(dur_ms)
            if self.logger is not None:
                with self._seen_lock:
                    emit = name not in self._seen
                    if emit:
                        self._seen.add(name)
                if emit:
                    self.logger.log(
                        "span",
                        name=name,
                        span_id=sid,
                        parent_id=parent,
                        dur_ms=round(dur_ms, 3),
                        role=self.role,
                        **attrs,
                    )

    def reset_exemplars(self) -> None:
        with self._seen_lock:
            self._seen.clear()

    def span_stats(self, reset: bool = False) -> Dict[str, Dict[str, float]]:
        """{span_name: snapshot} for every span histogram this tracer's
        registry holds (any role — a run report wants all of them)."""
        out = {}
        for name, role, m in self.registry.collect():
            if name.startswith("span_") and m.kind == "histogram":
                key = name[len("span_"):]
                if role and role != self.role:
                    key = f"{key}@{role}"
                out[key] = m.snapshot(reset=reset)
        return out


class TraceWindow:
    """--trace-dir: arm a one-shot ``utils.profiling.device_trace`` capture
    around learn steps [start_step, start_step + num_steps).

    The loops call ``step(learn_step)`` after every completed learn step;
    the window opens the first time the counter reaches ``start_step`` and
    closes ``num_steps`` later (or at ``close()``, so a short run still
    flushes a partial capture).  Resume-safe: a restored run whose counter
    is already past the window never arms.

    When the window closes the capture is reduced (obs/device_scopes.py) and
    logged as one 'device_time' row; the .xplane.pb stays where it is.  The
    scopes of the row come from the text of the compiled programs the loop
    registered with ``add_program``; the idle gaps outside a program run are
    named by ``tracer``'s spans, those inside one by scope path.  A capture
    with no device plane (the CPU backend) logs no row."""

    def __init__(self, logdir: str, start_step: int, num_steps: int,
                 logger=None, tracer: Optional["Tracer"] = None):
        self.logdir = logdir or None
        self.start_step = int(start_step)
        self.num_steps = max(int(num_steps), 1)
        self.logger = logger
        self.tracer = tracer
        self._programs: list = []
        self._armed = bool(self.logdir)
        self._stack: Optional[contextlib.ExitStack] = None
        self._opened_at: Optional[int] = None

    @property
    def active(self) -> bool:
        return self._stack is not None

    def add_program(self, module_text: Callable[[], str]) -> None:
        """Register a compiled program whose operations the 'device_time' row
        should put down to scopes: ``module_text()`` returns its text
        (``jitted.lower(*args).compile().as_text()``, which costs no compile
        for a program that has run) and is called only when a capture is
        reduced."""
        if self._armed:
            self._programs.append(module_text)

    def step(self, step: int) -> None:
        if not self._armed:
            return
        if self._stack is None and step >= self.start_step:
            if step >= self.start_step + self.num_steps:
                self._armed = False  # resumed past the window: never arm
                return
            from rainbow_iqn_apex_tpu.utils.profiling import device_trace

            self._stack = contextlib.ExitStack()
            self._stack.enter_context(device_trace(self.logdir))
            self._opened_at = step
            if self.logger is not None:
                self.logger.log("trace", event="trace_started", step=step,
                                logdir=self.logdir)
            return
        if self._stack is not None and step >= self._opened_at + self.num_steps:
            self._finish(step)

    def _finish(self, step: int) -> None:
        stack, self._stack = self._stack, None
        self._armed = False
        steps = step - (self._opened_at or step)
        try:
            stack.close()  # stops the profiler; writes the xplane artifacts
        finally:
            if self.logger is not None:
                self.logger.log("trace", event="trace_captured", step=step,
                                steps=steps, logdir=self.logdir)
        if self.logger is not None:
            row = self.device_time(steps)
            if row is not None:
                self.logger.log("device_time", step=step, **row)
        self._programs.clear()  # the closures hold the loop's frame

    def device_time(self, steps: int) -> Optional[Dict[str, Any]]:
        """The payload of the 'device_time' row for the capture in
        ``logdir``, which held ``steps`` learn steps; None where the capture
        holds no device plane.  Never raises: a reduction that fails says so
        in the row it returns."""
        from rainbow_iqn_apex_tpu.obs import device_scopes

        try:
            events = device_scopes.load_capture(self.logdir)
            red = events and device_scopes.reduce_events(
                events, [text() for text in self._programs],
                self.tracer.names if self.tracer is not None else ())
        except Exception as e:  # a broken capture must not end the run
            return {"steps": steps, "error": f"{type(e).__name__}: {e}"}
        if not red:
            return None
        ms = lambda seconds, per: round(1e3 * seconds / max(per, 1), 6)  # noqa: E731
        note = {}
        if self._programs and not red["scoped_instructions"]:
            # the cache's key leaves metadata out, so an executable cached
            # before the scopes (or before a rename) is loaded with its own
            note["note"] = ("the compiled text names no scope: the executable "
                            "came from a compile cache written without them; "
                            "clear the cache directory")
        return {
            **note,
            "steps": steps,
            "dispatches": red["dispatches"],
            "chips": red["chips"],
            "window_s": red["window_s"],
            "busy_s": red["busy_s"],
            "idle_share": round(red["idle_share"], 4),
            "programs": red["programs"],
            "scope_ms_per_step": {
                k: ms(v, steps) for k, v in sorted(red["by_scope"].items())},
            "path_ms_per_step": {
                k: ms(v, steps) for k, v in sorted(red["by_path"].items())},
            "outside_tick_ms_per_dispatch": ms(
                red["outside_tick_s"], red["dispatches"]),
            # of it, what wears a scope outside every tick: all of a
            # host-fed loop's programs, which have no tick
            "outside_path_ms_per_step": {
                k: ms(v, steps)
                for k, v in sorted(red["outside_by_path"].items())},
            "unresolved_share": round(
                100.0 * red["unresolved_s"] / red["total_s"], 4)
            if red["total_s"] else 0.0,
            "idle_gaps": red["idle_gaps"],
            "idle_gap_ms_by_span": red["idle_gap_ms_by_span"],
            # the other half of the account: idle inside a dispatch by the
            # scope path of the op that ends each gap, and what the compiler
            # made, by the path of the op that reads it
            "idle_ms_by_path_per_step": {
                k: ms(v, steps)
                for k, v in sorted(red["idle_s_by_path"].items())},
            "idle_between_dispatches_s": round(
                red["idle_between_dispatches_s"], 9),
            "compiler_made_ms_per_dispatch": ms(
                red["compiler_made_s"], red["dispatches"]),
            "compiler_made_ms_by_consumer_path_per_dispatch": {
                k: ms(v, red["dispatches"]) for k, v in sorted(
                    red["compiler_made_by_consumer_path"].items())},
            "compiler_made": [
                {"instruction": inst, "ms": round(1e3 * t, 6),
                 "opcode": opcode, "shape": shape, "consumer": consumer}
                for inst, t, opcode, shape, consumer
                in red["compiler_made"][:16]],
        }

    def close(self, step: int = 0) -> None:
        if self._stack is not None:
            self._finish(step or ((self._opened_at or 0) + 1))


# --------------------------------------------------------------------------
# jax-side gauges: compile counts + device memory
# --------------------------------------------------------------------------

_compile_registries: "weakref.WeakSet[MetricRegistry]" = weakref.WeakSet()
_compile_listener_attempted = False
_compile_listener_installed = False
_compile_lock = threading.Lock()


# the events of this JAX (0.9): `compiler.compile_or_get_cached` runs inside
# one BACKEND_COMPILE duration, be it a backend compile or a load from the
# persistent cache, and records CACHE_HIT inside it when it was a load
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
_pending_hit = threading.local()  # both events come from the compiling thread


def install_compile_counter(registry: MetricRegistry) -> bool:
    """Count into ``registry`` (role "jax") the programs this process had to
    compile, ``jax_compiles_total`` with their seconds in ``jax_compile_s``,
    and apart from them the programs it loaded from the persistent
    compilation cache, ``jax_compile_cache_hits_total``.  A run on a warm
    cache shows hits and no compiles; a compile after warm-up is a retrace.

    jax.monitoring has no unregister, so ONE module-level listener fans out
    to a WeakSet of live registries — per-run registries drop out when their
    run ends instead of leaking listeners across the test suite.
    Registration is attempted exactly once per process: a partially
    successful attempt (API drift on one of the two hooks) must never be
    retried, or the surviving hook would be registered again on every run
    and multiply the counts."""
    global _compile_listener_attempted, _compile_listener_installed
    with _compile_lock:
        _compile_registries.add(registry)
        if _compile_listener_attempted:
            return _compile_listener_installed
        _compile_listener_attempted = True

        def _on_event(event: str, **kw) -> None:
            if event != CACHE_HIT:
                return
            _pending_hit.value = True
            for reg in list(_compile_registries):
                reg.counter("jax_compile_cache_hits_total", "jax").inc()

        def _on_duration(event: str, duration: float, **kw) -> None:
            if event != BACKEND_COMPILE:
                return
            if getattr(_pending_hit, "value", False):
                _pending_hit.value = False  # this one was a load, not a compile
                return
            for reg in list(_compile_registries):
                reg.counter("jax_compiles_total", "jax").inc()
                reg.histogram("jax_compile_s", "jax").observe(duration)

        try:
            from jax import monitoring

            monitoring.register_event_listener(_on_event)
            monitoring.register_event_duration_secs_listener(_on_duration)
            _compile_listener_installed = True
        except Exception:  # pragma: no cover - older/newer jax API drift
            pass
        return _compile_listener_installed


def sample_device_gauges(registry: MetricRegistry, role: str = "") -> list:
    """Device-memory gauges over every local device.  Each gauge holds the
    fullest chip's figure (the one an OOM would hit first), and
    ``device_bytes_in_use_min`` the emptiest chip's, so replay or batches
    piling onto one chip of a mesh show as a gap between the two.  Returns
    the per-device ``bytes_in_use`` list for the timing row.  memory_stats()
    is None on CPU — a no-op there (the gauges simply never appear)."""
    stats = [d.memory_stats() for d in jax.local_devices()]
    if not all(stats):
        return []
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
        if all(key in s for s in stats):
            registry.gauge(f"device_{key}", role).set(
                float(max(s[key] for s in stats)))
    in_use = [int(s.get("bytes_in_use", 0)) for s in stats]
    registry.gauge("device_bytes_in_use_min", role).set(float(min(in_use)))
    return in_use
