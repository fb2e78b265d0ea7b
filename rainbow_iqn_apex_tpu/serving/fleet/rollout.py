"""Fleet-wide weight rollout: one versioned publish, N engines, no rollback.

The training side already made single-engine swaps safe: `CheckpointWatcher`
refuses ``older_than_loaded`` steps, `WeightMailbox` versions every publish,
and the `StalenessFence` pauses anything lagging past budget (PR 4).  This
module lifts those guarantees to a FLEET:

- ``publish(params, version)`` assigns a strictly increasing fleet version
  (a backward or duplicate version is refused with a ``rollout`` row, the
  fleet-level mirror of the engine's own older_than_loaded check — the two
  layers together make a rollback impossible even under a confused
  controller);
- the publish fans out to every attached engine via ``FleetEngine.adopt``
  (engines discovered later — scale-out, respawn — are caught up by
  ``sync()``, which the router's housekeeping or the autoscaler calls after
  membership changes);
- convergence is observable: ``converged()`` is true when every ROUTABLE
  engine serves the target, and the ``rollout`` row stream records
  publish -> adopt counts -> converged with the wall-clock convergence time
  (obs_report's ``fleet:`` section reads it back).

The router closes the loop: engines behind ``max_weight_lag`` publishes are
fenced out of dispatch, so a straggler engine degrades capacity, never
answer freshness.

**Delta-compressed rollouts** (``compression="int8_delta"``,
utils/quantize.py): instead of handing every engine the full params tree,
``publish`` encodes one `WeightPacket` — a periodic full base snapshot plus
int8 per-tensor deltas against the last reconstruction — and fans THAT out
(`FleetEngine.adopt_packet`); at fleet scale the broadcast cost drops >=3x
vs fp32 full publishes
(tests/test_quantize.py::test_delta_bytes_beat_fp32_3x).  Packet
application is bit-exact and versioned, so monotonicity, backward refusal
and the staleness fence are untouched; late joiners and gap-hit engines
are caught up by ``sync()`` replaying the chain-from-base.
``compression="off"`` (default) fans out the raw params object exactly as
before.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

from rainbow_iqn_apex_tpu.serving.fleet.registry import FleetEngine
from rainbow_iqn_apex_tpu.utils.quantize import DeltaEncoder, tree_bytes


class FleetRollout:
    """Versioned, monotone, fan-out weight publication over a fleet.

    Engines register with ``track(engine)`` (a `FleetEngine` or anything
    with ``adopt(params, version)`` + ``engine_id`` + a liveness-bearing
    ``transport``).  The controller keeps the params of the CURRENT target
    so late joiners can be synced without a re-publish.
    """

    def __init__(self, logger=None, obs_registry=None,
                 clock: Callable[[], float] = time.monotonic,
                 compression: str = "off", base_interval: int = 10,
                 tracer=None):
        self.logger = logger
        self.obs_registry = obs_registry
        self.clock = clock
        # pipeline tracing (obs/pipeline_trace.py): per-engine publish ->
        # adopt lag lands on the tracer's consumer windows (`lag` row /
        # RunHealth propagation budget); sampled versions emit adopt spans
        # under the cross-process "w<host>-<version>" trace id
        self.tracer = tracer
        self.compression = compression
        self._codec = (DeltaEncoder(base_interval)
                       if compression == "int8_delta" else None)
        self._lock = threading.Lock()
        self._engines: Dict[int, Any] = {}
        self.target_version = 0
        self._target_params: Any = None
        self._t_publish: Optional[float] = None
        self._converged_emitted = True
        self.refused = 0
        self.publishes = 0
        self.bytes_total = 0

    # ------------------------------------------------------------- membership
    def track(self, engine: FleetEngine) -> None:
        with self._lock:
            self._engines[int(engine.engine_id)] = engine

    def untrack(self, engine_id: int) -> None:
        with self._lock:
            self._engines.pop(int(engine_id), None)

    def version(self) -> int:
        """The rollout target — what the router's staleness fence measures
        engine lag against."""
        return self.target_version

    def reconstructed_digest(self) -> Optional[str]:
        """sha256 of the fp32 tree every in-sync subscriber should hold —
        compared against each engine's ``served_digest`` to assert the
        cross-host rollout landed bit-exact (net_smoke's convergence gate).
        Compressed rollouts digest the encoder's closed-loop reconstruction;
        uncompressed ones digest the target params directly.  None before
        the first publish."""
        from rainbow_iqn_apex_tpu.utils.quantize import tree_digest

        with self._lock:
            if self._codec is not None and self._codec.version >= 0:
                return tree_digest(self._codec.reconstructed())
            if self._target_params is not None:
                return tree_digest(self._target_params)
        return None

    # ---------------------------------------------------------------- publish
    def _row(self, event: str, **fields: Any) -> Dict[str, Any]:
        row = {"event": event, "version": self.target_version, **fields}
        if self.logger is not None:
            self.logger.log("rollout", **row)
        return row

    def publish(self, params: Any, version: Optional[int] = None) -> Dict[str, Any]:
        """Fan a new weight version out to every tracked engine.

        ``version`` defaults to target+1; an explicit version must be
        STRICTLY greater than the current target — the fleet never moves
        backwards, and a duplicate publish is a controller bug, not a no-op
        to paper over."""
        with self._lock:
            new_version = (self.target_version + 1 if version is None
                           else int(version))
            if new_version <= self.target_version:
                self.refused += 1
                row = self._row("refused_backward", refused=new_version,
                                target=self.target_version)
                if self.obs_registry is not None:
                    self.obs_registry.counter(
                        "rollout_refused_total", "rollout").inc()
                return row
            self.target_version = new_version
            self._target_params = params
            self._t_publish = self.clock()
            self._converged_emitted = False
            self.publishes += 1
            # delta compression: encode ONCE under the lock (the encoder is
            # closed-loop stateful — a racing second publish must see the
            # chain this one appended), fan the value-object packet out to N
            # engines lock-free below
            packet = (self._codec.encode(params, new_version)
                      if self._codec is not None else None)
            engines = list(self._engines.values())
        if self.obs_registry is not None:
            self.obs_registry.gauge("rollout_target_version", "rollout").set(
                self.target_version)
        if self.tracer is not None:
            self.tracer.note_publish(new_version)
        adopted, failed = self._fan_out(engines, params, new_version, packet)
        bytes_fp32 = tree_bytes(params)
        shipped = packet.nbytes() if packet is not None else bytes_fp32
        self.bytes_total += shipped
        if self.obs_registry is not None:
            self.obs_registry.counter(
                "publish_bytes_total", "rollout").inc(shipped)
        row = self._row("publish", engines=len(engines), adopted=adopted,
                        failed=failed, bytes=shipped, bytes_fp32=bytes_fp32,
                        compression=self.compression)
        self.maybe_emit_converged()
        return row

    def _fan_out(self, engines: List[Any], params: Any, version: int,
                 packet: Any = None) -> "tuple[int, int]":
        adopted = failed = 0
        for engine in engines:
            try:
                t0 = time.time()
                if packet is not None and hasattr(engine, "adopt_packet"):
                    engine.adopt_packet(packet)
                else:
                    engine.adopt(params, version)
                adopted += 1
                if self.tracer is not None:
                    eid = int(getattr(engine, "engine_id", -1))
                    self.tracer.note_adopt(f"engine{eid}", version)
                    if self.tracer.sampled(version):
                        self.tracer.emit_span(
                            "adopt", self.tracer.trace_id("w", version), t0,
                            version=version, consumer=f"engine{eid}",
                        )
            except Exception:
                # a failed adopt (dying engine, mid-kill race, or a
                # delta-chain gap on an engine that missed packets) is not
                # fatal to the rollout: the router fences the straggler and
                # sync() retries it; the publish row carries the count
                failed += 1
        return adopted, failed

    def sync(self) -> int:
        """Catch up engines behind the current target (late joiners from
        scale-out or respawn).  Returns how many adopted.  Compressed
        rollouts replay the chain-from-base (`adopt_chain` skips packets an
        engine already holds, so catch-up is idempotent and bit-exact)."""
        with self._lock:
            if self._target_params is None:
                return 0
            params, version = self._target_params, self.target_version
            chain = self._codec.chain() if self._codec is not None else None
            behind = [e for e in self._engines.values()
                      if e.transport.version() < version]
        adopted = 0
        for engine in behind:
            try:
                if chain is not None and hasattr(engine, "adopt_chain"):
                    engine.adopt_chain(chain)
                else:
                    engine.adopt(params, version)
                adopted += 1
            except Exception:
                pass  # still behind; the next sync retries
        if adopted:
            self._row("sync", adopted=adopted)
        self.maybe_emit_converged()
        return adopted

    # ------------------------------------------------------------ convergence
    def engine_versions(self) -> Dict[int, int]:
        with self._lock:
            return {eid: e.transport.version()
                    for eid, e in self._engines.items()}

    def converged(self) -> bool:
        """Every LIVE tracked engine serves the target version, and at
        least ONE does.  Dead engines don't block convergence — their lease
        eviction removes them from routing, and a respawn re-enters through
        sync() — but a fleet with NOTHING live serving the target has not
        converged: an all-engines-down publish must not emit a bogus
        converged row the moment it lands."""
        with self._lock:
            engines = list(self._engines.values())
            target = self.target_version
        if target <= 0:
            return True  # nothing ever published: vacuously converged
        live = [e for e in engines if e.transport.alive()]
        if not live:
            return False
        return all(e.transport.version() >= target for e in live)

    def maybe_emit_converged(self) -> Optional[Dict[str, Any]]:
        """Emit the one ``converged`` row per publish (idempotent)."""
        with self._lock:
            if self._converged_emitted or self._t_publish is None:
                return None
        if not self.converged():
            return None
        with self._lock:
            if self._converged_emitted:
                return None
            self._converged_emitted = True
            dt = self.clock() - self._t_publish
        if self.obs_registry is not None:
            self.obs_registry.gauge(
                "rollout_convergence_s", "rollout").set(round(dt, 3))
        return self._row("converged", convergence_s=round(dt, 3),
                         versions={str(k): v
                                   for k, v in self.engine_versions().items()})

    def wait_converged(self, timeout_s: float = 10.0,
                       poll_s: float = 0.05) -> bool:
        """Poll-with-sync until the fleet converges or the budget runs out."""
        deadline = self.clock() + float(timeout_s)
        while True:
            self.sync()
            if self.converged():
                self.maybe_emit_converged()
                return True
            if self.clock() >= deadline:
                return False
            time.sleep(poll_s)
