"""PolicyServer: batched low-latency `act(observation) -> action` for many
concurrent clients, with weight hot-swap.

Composition (one worker thread owns the device; clients only touch the
queue):

    client threads --submit--> MicroBatcher (bounded queue, deadline)
                                   |
                              worker thread --pad to bucket--> InferenceEngine
                                   |                               ^
                              fulfil futures             CheckpointWatcher /
                              + ServeMetrics             reload() hot-swap

Transport is in-process by design: the Ape-X mesh already colocates acting
with the chips, so the serving seam is a Python API that a network front-end
(or the actor loop itself) calls.  Everything latency-relevant — coalescing,
padding, shedding, swap — is below this seam and covered by tier-1 CPU
tests; a socket listener is a thin adapter on top.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.obs.export import ObsHTTPServer
from rainbow_iqn_apex_tpu.serving.batcher import (
    MicroBatcher,
    ServeFuture,
    ServerClosed,
)
from rainbow_iqn_apex_tpu.serving.engine import InferenceEngine, parse_buckets
from rainbow_iqn_apex_tpu.serving.metrics import ServeMetrics
from rainbow_iqn_apex_tpu.serving.swap import (
    CheckpointWatcher,
    params_template,
    restore_params,
)
from rainbow_iqn_apex_tpu.utils.checkpoint import Checkpointer
from rainbow_iqn_apex_tpu.utils.compile_cache import enable_compile_cache
from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger


class PolicyServer:
    """Serve IQN policy inference to concurrent clients.

    Lifecycle: construct -> start() -> submit()/act() from any thread ->
    stop().  stop() drains queued requests before exiting (graceful), unless
    ``drain=False`` fails them immediately.
    """

    def __init__(
        self,
        cfg: Config,
        num_actions: int,
        params: Any,
        devices: Optional[Sequence[jax.Device]] = None,
        checkpointer: Optional[Checkpointer] = None,
        state_shape: Optional[Tuple[int, ...]] = None,
        template: Optional[Any] = None,
        metrics_path: Optional[str] = None,
        echo_metrics: bool = False,
    ):
        enable_compile_cache()  # before the engine's first bucket compiles
        self.cfg = cfg
        self.num_actions = num_actions
        self.metrics = ServeMetrics(
            MetricsLogger(metrics_path, run_id=cfg.run_id, echo=echo_metrics)
            if metrics_path
            else None
        )
        # live fleet telemetry (obs/net/): serving hosts stream their rows
        # + registry snapshots to the fleet collector too; None (nothing
        # constructed) whenever the plane is off or there is no logger
        self.obs_relay = None
        if self.metrics.logger is not None and getattr(cfg, "obs_net", False):
            from rainbow_iqn_apex_tpu.obs.net.relay import ObsRelay

            self.obs_relay = ObsRelay.attach(
                cfg, self.metrics.logger, registry=self.metrics.registry,
                role="serve")
        self._obs_shape_early = tuple(state_shape or cfg.state_shape)
        # calibration for the quantization agreement gate: callers with real
        # traffic/replay frames pass them via engine.set_calibration; the
        # default synthesizes seeded uniform frames, which exercise the full
        # numeric path (conv -> taus -> heads) even if they are not the
        # served distribution (docs/PERFORMANCE.md "quantization")
        calib_obs = None
        if getattr(cfg, "serve_quantize", "off") != "off":
            n = max(int(getattr(cfg, "quant_calib_batch", 64)), 1)
            calib_obs = np.random.default_rng(cfg.seed + 7).integers(
                0, 255, (n, *self._obs_shape_early), dtype=np.uint8
            )
        self.engine = InferenceEngine(
            cfg,
            num_actions,
            params,
            devices=devices,
            buckets=parse_buckets(cfg.serve_batch_buckets),
            mode=cfg.serve_mode,
            calib_obs=calib_obs,
            quant_log=self._quant_log,
        )
        self.batcher = MicroBatcher(
            self.engine.buckets,
            deadline_s=cfg.serve_deadline_ms / 1e3,
            queue_bound=cfg.serve_queue_bound,
            metrics=self.metrics,
        )
        self.watcher: Optional[CheckpointWatcher] = None
        self._owns_checkpointer = False  # from_checkpoint sets it; stop() closes
        if checkpointer is not None:
            self.watcher = CheckpointWatcher(
                checkpointer,
                template if template is not None
                else params_template(cfg, num_actions, state_shape=state_shape),
                self.engine.load_params,
                poll_interval_s=cfg.serve_swap_poll_s,
                metrics=self.metrics,
            )
        self._obs_shape = tuple(state_shape or cfg.state_shape)
        self._metrics_interval_s = max(cfg.serve_metrics_interval_s, 0.0)
        self._worker: Optional[threading.Thread] = None
        self._started = False
        # obs/: /metrics (Prometheus text off the shared registry ServeMetrics
        # records into) + /healthz (shed/queue/worker-liveness status)
        self.obs_http: Optional[ObsHTTPServer] = None
        if int(getattr(cfg, "obs_http_port", 0) or 0) > 0:
            self.obs_http = ObsHTTPServer(
                self.metrics.registry, self.healthz, port=cfg.obs_http_port
            )

    def _quant_log(self, kind: str, **fields: Any) -> None:
        """Engine gate events -> the shared metrics surface: schema rows
        (`quant` / `quant_fallback`) plus registry gauges so /metrics and
        RunHealth see the same numbers."""
        reg = self.metrics.registry
        if kind == "quant_fallback":
            reg.counter("quant_fallback_total", "serve").inc()
        if fields.get("agreement") is not None:
            reg.gauge("quant_action_agreement", "serve").set(
                float(fields["agreement"]))
        if self.metrics.logger is not None:
            self.metrics.logger.log(kind, **fields)

    @classmethod
    def from_checkpoint(
        cls,
        cfg: Config,
        num_actions: int,
        checkpoint_dir: str,
        state_shape: Optional[Tuple[int, ...]] = None,
        **kwargs: Any,
    ) -> "PolicyServer":
        """Boot a server straight off a learner's checkpoint directory; the
        watcher then follows that directory for newer steps."""
        ckpt = Checkpointer(checkpoint_dir)
        # one template: init_train_state is a full network+optimizer trace,
        # too expensive to rebuild again inside __init__ for the watcher
        try:
            template = params_template(cfg, num_actions, state_shape=state_shape)
            params = restore_params(ckpt, template)
        except BaseException:
            ckpt.close()  # a supervisor retrying boot must not leak managers
            raise
        server = cls(
            cfg,
            num_actions,
            params,
            checkpointer=ckpt,
            state_shape=state_shape,
            template=template,
            **kwargs,
        )
        server._owns_checkpointer = True
        server.watcher.last_step = ckpt.latest_step()
        return server

    # -------------------------------------------------------------- lifecycle
    def warmup(self) -> int:
        """Compile every bucket's executable now, not on first live traffic —
        an uncompiled bucket charges full XLA compile time (well past act()'s
        default timeout on a real network) to whichever request hits it first,
        and corrupts the latency percentiles.  Idempotent; returns the bucket
        count."""
        for b in self.engine.buckets:
            self.engine.infer(np.zeros((b, *self._obs_shape), np.uint8))
        return len(self.engine.buckets)

    def start(self, warmup: bool = True) -> "PolicyServer":
        if self._started:
            return self
        if warmup:
            self.warmup()
        self._started = True
        self._worker = threading.Thread(
            target=self._serve_loop, name="serve-worker", daemon=True
        )
        self._worker.start()
        if self.watcher is not None:
            self.watcher.start()
        if self.obs_http is not None:
            self.obs_http.start()
        return self

    def stop(self, drain: bool = True) -> Dict[str, Any]:
        """Shut down: refuse new requests, drain (or fail) queued ones, emit
        a final metrics row.  Returns lifetime stats."""
        self.batcher.close()
        if not drain:
            self.batcher.abort_pending(ServerClosed("server stopped"))
        if self._worker is not None:
            self._worker.join(timeout=60)
            self._worker = None
        # whatever is STILL queued (never started, or the join timed out on a
        # wedged worker) fails promptly instead of hanging its clients until
        # their own result() timeouts
        self.batcher.abort_pending(ServerClosed("server stopped"))
        if self.watcher is not None:
            self.watcher.stop()
            if self._owns_checkpointer:
                self._owns_checkpointer = False  # idempotent double-stop
                self.watcher.ckpt.close()
        if self.obs_http is not None:
            self.obs_http.stop()
        self.metrics.emit(final=True)
        if self.obs_relay is not None:
            self.obs_relay.close()  # drains the final row before the close
            self.obs_relay = None
        if self.metrics.logger is not None:
            self.metrics.logger.close()
        return self.metrics.stats()

    def __enter__(self) -> "PolicyServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------------ client API
    def submit(self, obs: np.ndarray) -> ServeFuture:
        """Enqueue one observation [H, W, C] uint8; returns a future.
        Raises ServerOverloaded when the queue is at its bound (shed) and
        ServerClosed after stop().  Shape/dtype are validated HERE, in the
        caller's thread — a malformed observation must fail its own client,
        never reach the worker's batch assembly."""
        arr = np.asarray(obs)
        if tuple(arr.shape) != self._obs_shape:
            raise ValueError(
                f"observation shape {tuple(arr.shape)} != served {self._obs_shape}"
            )
        if arr.dtype != np.uint8:
            # silent uint8 truncation would turn normalized float frames
            # into all-zero pixels and confidently wrong actions
            raise TypeError(f"observations must be uint8 frames, got {arr.dtype}")
        return self.batcher.submit(arr)

    def try_submit(self, obs: np.ndarray) -> Optional[ServeFuture]:
        """submit() that returns None on a full queue instead of recording a
        shed — for the fleet router's multi-engine dispatch probes (the
        router owns the shed story; see MicroBatcher.try_submit)."""
        arr = np.asarray(obs)
        if tuple(arr.shape) != self._obs_shape:
            raise ValueError(
                f"observation shape {tuple(arr.shape)} != served {self._obs_shape}"
            )
        if arr.dtype != np.uint8:
            raise TypeError(f"observations must be uint8 frames, got {arr.dtype}")
        return self.batcher.try_submit(arr)

    def act(self, obs: np.ndarray, timeout: Optional[float] = 30.0) -> int:
        """Blocking convenience: one observation in, one action out."""
        action, _ = self.act_values(obs, timeout)
        return action

    def act_values(
        self, obs: np.ndarray, timeout: Optional[float] = 30.0
    ) -> Tuple[int, np.ndarray]:
        """Blocking act returning (action, expected Q per action [A]).
        A timed-out request is CANCELLED before the TimeoutError propagates:
        this client has given up, so the batcher must not pad, dispatch and
        fulfil its dead slot (counted as serve_cancelled_total)."""
        fut = self.submit(obs)
        try:
            return fut.result(timeout)
        except TimeoutError:
            fut.cancel()
            raise

    def reload(self, step: Optional[int] = None, force: bool = False) -> Dict[str, Any]:
        """Explicit hot-swap from the watched checkpoint dir."""
        if self.watcher is None:
            raise RuntimeError("server was built without a checkpointer")
        return self.watcher.reload(step=step, force=force)

    def load_params(self, params: Any) -> int:
        """Direct hot-swap from an in-memory params tree (the learner-process
        colocated path: no checkpoint round-trip)."""
        version = self.engine.load_params(params)
        self.metrics.record_swap(ok=True, params_version=version, source="direct")
        return version

    def healthz(self) -> Dict[str, Any]:
        """Live status for /healthz: failing = the worker thread died under a
        started server (nothing will drain the queue); degraded = shedding in
        the current window or the queue is within 20% of its shed bound."""
        snap = self.metrics.snapshot()
        depth = self.batcher.depth()
        worker_alive = self._worker is not None and self._worker.is_alive()
        status = "ok"
        if snap.get("shed", 0) > 0 or depth >= 0.8 * self.cfg.serve_queue_bound:
            status = "degraded"
        if self._started and not worker_alive:
            status = "failing"
        return {
            "status": status,
            "queue_depth": depth,
            "worker_alive": worker_alive,
            "params_version": self.engine.params_version,
            # serving staleness, externally monitorable (the serving mirror
            # of the actor-side weight_version_lag gauge): which weight
            # version is live and how long since it changed
            "weights_version": self.engine.params_version,
            "weights_age_s": round(self.engine.weights_age_s(), 3),
            "weights_step": None if self.watcher is None
            else self.watcher.last_step,
            # quantized-inference status (docs/SERVING.md): which numeric
            # path is live and the last gate's agreement
            **self.engine.quant_state(),
            **snap,
        }

    def stats(self) -> Dict[str, Any]:
        return {
            "queue_depth": self.batcher.depth(),
            "params_version": self.engine.params_version,
            "compiled_executables": self.engine.compiled_executables(),
            "buckets": self.engine.buckets,
            **self.engine.quant_state(),
            **self.metrics.stats(),
        }

    # ------------------------------------------------------------ worker loop
    def _serve_loop(self) -> None:
        last_emit = time.monotonic()
        # idle timeout = metrics interval: take() returns [] on a quiet
        # queue so the heartbeat row below still fires with zero traffic
        # (a consumer must be able to tell "up, idle" from "dead")
        idle_s = self._metrics_interval_s or None
        while True:
            batch = self.batcher.take(idle_timeout_s=idle_s)
            if batch is None:  # closed and drained
                break
            if batch:
                try:
                    obs = np.stack([f.obs for f in batch])
                    actions, qs = self.engine.infer(obs)
                except Exception as e:  # fail the batch, keep serving
                    for fut in batch:
                        fut.set_error(e)
                else:
                    for i, fut in enumerate(batch):
                        fut.set_result(int(actions[i]), qs[i])
                        self.metrics.record_latency_ms(fut.latency_ms)
            now = time.monotonic()
            if self._metrics_interval_s and now - last_emit >= self._metrics_interval_s:
                last_emit = now
                try:
                    self.metrics.emit(queue_depth=self.batcher.depth())
                except Exception:  # a metrics I/O failure (disk full on the
                    pass           # JSONL path) must never kill the worker
