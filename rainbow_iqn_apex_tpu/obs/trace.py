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

Also here: the jax-side gauges (compile/retrace counts via jax.monitoring,
device memory via Device.memory_stats) and TraceWindow — the step-windowed
profiler capture that finally wires utils/profiling.device_trace into the
train loops (--trace-dir; the hooks were dead code before this).
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
import weakref
from typing import Any, Dict, Optional

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

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any):
        sid = next(_span_ids)
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
    is already past the window never arms."""

    def __init__(self, logdir: str, start_step: int, num_steps: int,
                 logger=None):
        self.logdir = logdir or None
        self.start_step = int(start_step)
        self.num_steps = max(int(num_steps), 1)
        self.logger = logger
        self._armed = bool(self.logdir)
        self._stack: Optional[contextlib.ExitStack] = None
        self._opened_at: Optional[int] = None

    @property
    def active(self) -> bool:
        return self._stack is not None

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
        try:
            stack.close()  # stops the profiler; writes the xplane artifacts
        finally:
            if self.logger is not None:
                self.logger.log("trace", event="trace_captured", step=step,
                                steps=step - (self._opened_at or step),
                                logdir=self.logdir)

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


def install_compile_counter(registry: MetricRegistry) -> bool:
    """Count XLA compiles/retraces into ``jax_compiles_total`` (role "jax").

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
            if "compil" not in event:
                return
            for reg in list(_compile_registries):
                reg.counter("jax_compiles_total", "jax").inc()

        def _on_duration(event: str, duration: float, **kw) -> None:
            if "compil" not in event:
                return
            for reg in list(_compile_registries):
                reg.histogram("jax_compile_s", "jax").observe(duration)

        try:
            from jax import monitoring

            monitoring.register_event_listener(_on_event)
            _compile_listener_installed = True
        except Exception:  # pragma: no cover - older/newer jax API drift
            pass
        try:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(_on_duration)
            _compile_listener_installed = True
        except Exception:  # pragma: no cover
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
