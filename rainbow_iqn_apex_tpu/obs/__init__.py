"""obs/ — unified telemetry for every role in the system.

One schema (obs/schema.py) over one funnel (utils.logging.MetricsLogger),
fed by one process-wide metric surface:

  registry.py   named counters/gauges/histograms with role labels
  trace.py      `with span("learn_step"):` host spans aligned with XLA
                traces, jax compile counters, device-memory gauges, and the
                --trace-dir step-windowed profiler capture
  device_scopes.py  the names the compiled programs wrap their work in
                (jax.named_scope), and device time of a capture by name
  health.py     heartbeats + fault rows + stalls + sheds folded into one
                periodic 'health' row with status in {ok, degraded, failing}
  export.py     Prometheus text exposition + stdlib /metrics + /healthz

RunObs below is the per-run bundle the train loops construct right after
their MetricsLogger; scripts/obs_report.py is the offline consumer that
turns a run dir's JSONL back into a report.  docs/OBSERVABILITY.md is the
schema reference.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from rainbow_iqn_apex_tpu.obs.export import ObsHTTPServer, prometheus_text
from rainbow_iqn_apex_tpu.obs.health import RunHealth
from rainbow_iqn_apex_tpu.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
)
from rainbow_iqn_apex_tpu.obs.pipeline_trace import (
    PipelineTracer,
    critical_path,
    format_critical_path,
)
from rainbow_iqn_apex_tpu.obs.registry import get as get_registry
from rainbow_iqn_apex_tpu.obs.registry import reset_global as reset_global_registry
from rainbow_iqn_apex_tpu.obs.schema import (
    REQUIRED_KEYS,
    SCHEMA_VERSION,
    sanitize,
    validate_row,
)

# obs.trace imports jax; resolve its names lazily (PEP 562) so jax-free
# consumers (schema/registry/health users like the chaos-soak processes)
# can import the package without paying the device-runtime import.
_TRACE_EXPORTS = (
    "TraceWindow",
    "Tracer",
    "install_compile_counter",
    "sample_device_gauges",
)


def __getattr__(name: str):
    if name in _TRACE_EXPORTS:
        import importlib

        return getattr(
            importlib.import_module("rainbow_iqn_apex_tpu.obs.trace"), name
        )
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricRegistry",
    "ObsHTTPServer",
    "PipelineTracer",
    "REQUIRED_KEYS",
    "RunHealth",
    "RunObs",
    "SCHEMA_VERSION",
    "TraceWindow",
    "Tracer",
    "critical_path",
    "format_critical_path",
    "get_registry",
    "install_compile_counter",
    "prometheus_text",
    "reset_global_registry",
    "sample_device_gauges",
    "sanitize",
    "validate_row",
]


class RunObs:
    """Everything one training run needs from obs/, in one object.

    Construct right after the MetricsLogger; the loops then touch four seams:

        obs = RunObs(cfg, metrics, role="learner")
        with obs.span("act"): ...                       # hot regions
        obs.after_learn_step(step)                      # per learn step
        obs.periodic(step, frames, replay_occupancy=x)  # at metrics cadence
        obs.close(step, frames)                         # at exit

    ``periodic`` emits the 'timing' row (StepTimer percentiles + span
    aggregates + compile counts) and the 'health' row, samples device-memory
    gauges, and re-arms span exemplars.  When cfg.obs_http_port > 0 the
    /metrics + /healthz endpoint is served for the run's lifetime."""

    def __init__(
        self,
        cfg,
        metrics,
        role: str = "learner",
        registry: Optional[MetricRegistry] = None,
        start_http: bool = True,
    ):
        from rainbow_iqn_apex_tpu.obs.trace import (
            TraceWindow,
            Tracer,
            install_compile_counter,
            sample_device_gauges,
        )
        from rainbow_iqn_apex_tpu.utils.profiling import StepTimer

        self._sample_device_gauges = sample_device_gauges

        self.cfg = cfg
        self.metrics = metrics
        self.role = role
        self.registry = registry if registry is not None else MetricRegistry()
        self.tracer = Tracer(self.registry, metrics, role)
        self.health = RunHealth(
            self.registry,
            metrics,
            role=role,
            max_nan_strikes=getattr(cfg, "max_nan_strikes", 3),
        )
        add_observer = getattr(metrics, "add_observer", None)
        if add_observer is not None:
            add_observer(self.health.observe_row)
        self.timer = StepTimer()
        self.trace_window = TraceWindow(
            getattr(cfg, "trace_dir", ""),
            getattr(cfg, "trace_start_step", 0),
            getattr(cfg, "trace_num_steps", 1),
            logger=metrics,
            tracer=self.tracer,
        )
        install_compile_counter(self.registry)
        self.http: Optional[ObsHTTPServer] = None
        port = int(getattr(cfg, "obs_http_port", 0) or 0)
        if start_http and port > 0:
            self.http = ObsHTTPServer(
                self.registry, self.health.healthz, port=port
            ).start()
        # live fleet telemetry (obs/net/; docs/OBSERVABILITY.md "Live fleet
        # telemetry"): with cfg.obs_net the run's rows + registry snapshots
        # also stream to the lease-discovered collector.  Lazy import keeps
        # the plane's code entirely off the default path (attach returns
        # None when the gate is off, so nothing is constructed either).
        self.relay = None
        if getattr(cfg, "obs_net", False):
            from rainbow_iqn_apex_tpu.obs.net.relay import ObsRelay

            self.relay = ObsRelay.attach(
                cfg, metrics, registry=self.registry, role=role)
        self._steps = self.registry.gauge("learn_step", role)
        self._frames = self.registry.gauge("frames", role)
        self._closed = False

    # ------------------------------------------------------------------ seams
    def span(self, name: str, **attrs: Any):
        return self.tracer.span(name, **attrs)

    def after_learn_step(self, step: int, block_on=None,
                         units: int = 1) -> None:
        """Per-learn-step bookkeeping: StepTimer lap + the --trace-dir
        window.  Leave ``block_on`` None when the loop already syncs on the
        step's scalars (NaN guard / priority write-back) or deliberately
        stays async (anakin) — a gratuitous barrier here would serialize
        the host against the device queue.  ``units`` = SGD steps the call
        covers (replay reuse dispatches K per call — the timing row's
        steps/steps_per_sec must count steps, not dispatches)."""
        self.timer.lap(block_on, units=units)
        self.health.note_finite_step()
        self.trace_window.step(step)

    def periodic(self, step: int, frames: int = 0, **gauges: Any) -> None:
        """Emit 'timing' + 'health' rows for the window ending now."""
        self._steps.set(step)
        self._frames.set(frames)
        device_bytes = self._sample_device_gauges(self.registry, self.role)
        stats = self.timer.stats()
        timing: Dict[str, Any] = {
            f"learn_{k}": round(float(v), 6) for k, v in stats.items()
        }
        timing["spans"] = {
            name: {k: round(float(v), 6) for k, v in snap.items()}
            for name, snap in self.tracer.span_stats(reset=True).items()
        }
        timing["compiles"] = int(
            self.registry.counter("jax_compiles_total", "jax").get()
        )
        timing["compile_cache_hits"] = int(
            self.registry.counter("jax_compile_cache_hits_total", "jax").get()
        )
        if device_bytes:  # per local device; absent on backends without stats
            timing["device_bytes_in_use"] = device_bytes
        self.metrics.log("timing", step=step, frames=frames, **timing)
        self.tracer.reset_exemplars()
        self.health.tick(step, frames, **gauges)

    def close(self, step: int = 0, frames: int = 0, **gauges: Any) -> None:
        """Final flush: close any open trace window, emit the last timing +
        health rows, stop the HTTP endpoint.  Idempotent."""
        if self._closed:
            return
        self._closed = True
        self.trace_window.close(step)
        try:
            self.periodic(step, frames, **gauges)
        finally:
            if self.relay is not None:
                self.relay.close()
                self.relay = None
            if self.http is not None:
                self.http.stop()
                self.http = None
