"""ObsRelay: stream this process's telemetry to the fleet obs collector.

The relay is an OBSERVER, never a participant: it attaches to the process's
``MetricsLogger`` via ``add_observer`` (every sanitized row dict lands in
``observe``) and, when a registry is attached, ships a periodic snapshot of
its counters/gauges/histograms.  Everything rides the netcore framed-socket
codec as header-only JSON frames:

    {op: "hello", host, role, run, pid}        once per connection
    {op: "rows", rows: [row, ...]}             coalesced logged rows
    {op: "snap", metrics: registry.as_dict()}  tier-2 registry snapshot

Non-negotiables, in priority order:

1. **Never stall the env/learn loop.**  ``observe`` is one bounded deque
   append under a lock — no socket I/O, no blocking.  A FULL spool sheds
   the NEWEST row with a counted, rate-limited reasoned `obs_net` row
   (the AppendClient shed story, telemetry edition).
2. **Never load-bearing.**  The local JSONL is written by MetricsLogger
   before observers run; a dead/wedged collector changes nothing about it.
   Delivery is at-most-once by design — the JSONL is the durable record,
   the wire is the live view.
3. **Reconnect rides the shared RetryPolicy.**  The collector is
   re-discovered from its `obs_collector` lease on every dial (it may have
   respawned elsewhere at a new addr:port), and the backoff schedule is
   clamped at its ceiling — a dead collector is retried forever; giving up
   is the operator's call, not the socket's.

jax-free: relays run inside every role, including device-less ones
(league controller, replay shard servers, standbys).
"""

from __future__ import annotations

import collections
import os
import socket
import threading
import time
from typing import Any, Dict, Optional, Tuple

from rainbow_iqn_apex_tpu.netcore import chaos, framing
from rainbow_iqn_apex_tpu.utils.faults import RetryPolicy

_SEND_TIMEOUT_S = 5.0  # blocking-with-a-bound: a wedged collector whose
# kernel buffer filled turns into a timeout -> disconnect -> spool/shed,
# never a worker thread stuck in sendall forever
_COALESCE_ROWS = 64  # rows per "rows" frame
_STATS_EVERY_S = 10.0  # periodic local `obs_net` stats row cadence
_SHED_LOG_EVERY_S = 5.0  # rate limit on the reasoned shed row


class ObsRelay:
    """Bounded non-blocking telemetry spool -> framed-socket stream.

    Construct via ``from_config`` (None when ``cfg.obs_net`` is off — the
    house default-off seam), then ``logger.add_observer(relay.observe)``.
    ``attach`` does both.  Direct ``collector_addr`` bypasses lease
    discovery (tests)."""

    def __init__(
        self,
        heartbeat_dir: str = "",
        host_id: int = 0,
        role: str = "",
        run_id: str = "",
        registry=None,
        logger=None,
        spool_rows: int = 2048,
        snapshot_s: float = 5.0,
        lease_timeout_s: float = 30.0,
        lease_skew_s: float = 0.0,
        retry: Optional[RetryPolicy] = None,
        collector_addr: Optional[Tuple[str, int]] = None,
    ):
        self.heartbeat_dir = heartbeat_dir
        self.host_id = int(host_id)
        self.role = str(role)
        self.run_id = str(run_id)
        self.registry = registry
        self.logger = logger
        self.spool_rows = max(int(spool_rows), 1)
        self.snapshot_s = float(snapshot_s)
        self.lease_timeout_s = float(lease_timeout_s)
        self.retry = retry if retry is not None else RetryPolicy(
            attempts=6, base_delay_s=0.2, max_delay_s=5.0)
        self._fixed_addr = collector_addr
        self._lock = threading.Lock()
        self._spool: "collections.deque" = collections.deque()
        self._wake = threading.Event()
        self._stop = threading.Event()
        # shared counters (observe()/worker both write) — under _lock
        self.spooled_rows = 0
        self.shed_rows = 0
        # worker-thread-only state/counters (stats() only reads them)
        self.sent_rows = 0
        self.sent_frames = 0
        self.snapshots_sent = 0
        self.reconnects = 0
        self.collector: str = ""  # "addr:port" of the last connection
        self._sock: Optional[socket.socket] = None
        self._ever_connected = False
        self._fail_streak = 0
        self._next_dial = 0.0
        self._delays = list(self.retry.delays()) or [self.retry.base_delay_s]
        self._last_snap = 0.0
        self._last_stats = time.monotonic()
        self._last_shed_log = 0.0  # observe()-side only (rate limit)
        self._in_shed_log = False  # observe()-side reentrancy guard
        self._monitor = None
        if heartbeat_dir and collector_addr is None:
            from rainbow_iqn_apex_tpu.parallel.elastic import HeartbeatMonitor

            self._monitor = HeartbeatMonitor(
                heartbeat_dir, lease_timeout_s, self_id=None,
                skew_tolerance_s=lease_skew_s)
        self._thread = threading.Thread(
            target=self._run, name=f"obsnet-relay-{role or host_id}",
            daemon=True)
        self._thread.start()

    # ------------------------------------------------------------- plumbing
    @classmethod
    def from_config(cls, cfg, logger=None, registry=None,
                    role: str = "learner") -> Optional["ObsRelay"]:
        """The default-off seam: None unless ``cfg.obs_net`` is set, so the
        no-flag path constructs nothing and stays bitwise the pre-plane
        behaviour."""
        if not getattr(cfg, "obs_net", False):
            return None
        from rainbow_iqn_apex_tpu.parallel.elastic import heartbeat_dir

        return cls(
            heartbeat_dir(cfg),
            host_id=getattr(cfg, "process_id", 0),
            role=role,
            run_id=getattr(cfg, "run_id", ""),
            registry=registry,
            logger=logger,
            spool_rows=getattr(cfg, "obs_net_spool", 2048),
            snapshot_s=getattr(cfg, "obs_net_snapshot_s", 5.0),
            lease_timeout_s=getattr(cfg, "heartbeat_timeout_s", 30.0),
            lease_skew_s=getattr(cfg, "lease_skew_tolerance_s", 0.0),
            retry=RetryPolicy(
                attempts=6,
                base_delay_s=getattr(cfg, "respawn_base_s", 0.2),
                max_delay_s=getattr(cfg, "respawn_max_s", 5.0),
                seed=getattr(cfg, "seed", 0),
            ),
        )

    @classmethod
    def attach(cls, cfg, logger, registry=None,
               role: str = "learner") -> Optional["ObsRelay"]:
        """from_config + add_observer in one call — the one-line seam every
        role's wiring uses."""
        relay = cls.from_config(cfg, logger=logger, registry=registry,
                                role=role)
        if relay is not None:
            add = getattr(logger, "add_observer", None)
            if add is not None:
                add(relay.observe)
        return relay

    def _log(self, event: str, **fields: Any) -> None:
        if self.logger is not None:
            try:
                self.logger.log("obs_net", event=event, relay=self.role,
                                collector=self.collector, **fields)
            except Exception:
                pass  # telemetry about telemetry must never raise

    # ------------------------------------------------------------- producer
    def observe(self, row: Dict[str, Any]) -> None:
        """MetricsLogger observer: spool one already-sanitized row.  Never
        blocks; a full spool sheds the newest row, counted + reasoned."""
        with self._lock:
            if self._in_shed_log:
                # the reasoned shed row below re-enters here through the
                # logger's observer fan-out; it is local-JSONL-only by
                # design (the spool that would carry it is the full one)
                return
            if len(self._spool) >= self.spool_rows:
                self.shed_rows += 1
                shed = self.shed_rows
            else:
                self._spool.append(dict(row))
                self.spooled_rows += 1
                shed = None
        if shed is None:
            self._wake.set()
            return
        if self.registry is not None:
            self.registry.counter("obsnet_shed_rows_total", "obs_net").inc()
        now = time.monotonic()
        if now - self._last_shed_log > _SHED_LOG_EVERY_S:
            self._last_shed_log = now  # unlocked-ok: observe() runs on the
            # logging thread only (MetricsLogger fans out synchronously)
            with self._lock:
                self._in_shed_log = True
            try:
                self._log("spool_shed", shed_rows=shed,
                          spool=self.spool_rows,
                          why="spool full: collector unreachable or rows "
                              "outpacing the wire; newest row dropped so "
                              "the training loop never waits on telemetry")
            finally:
                with self._lock:
                    self._in_shed_log = False

    def spool_depth(self) -> int:
        with self._lock:
            return len(self._spool)

    # ------------------------------------------------------------ transport
    def _discover(self) -> Optional[Tuple[str, int]]:
        """The freshest `obs_collector` lease's addr:port (highest epoch
        wins — a respawned collector supersedes its stale predecessor)."""
        if self._fixed_addr is not None:
            return self._fixed_addr
        if self._monitor is None:
            return None
        best = None
        for lease in self._monitor.leases().values():
            if (lease.role == "obs_collector" and lease.fresh
                    and lease.addr and lease.port):
                if best is None or lease.epoch > best.epoch:
                    best = lease
        return (best.addr, best.port) if best is not None else None

    def _dial(self) -> bool:
        """One bounded connect + hello; schedules backoff on failure."""
        addr = self._discover()
        if addr is None:
            self._backoff()
            return False
        try:
            sock = socket.create_connection(addr, timeout=_SEND_TIMEOUT_S)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            sock.settimeout(_SEND_TIMEOUT_S)
            sock = chaos.maybe_wrap(sock, peer="collector",
                                    logger=self.logger)
            framing.send_frame(sock, {
                "op": "hello", "host": self.host_id, "role": self.role,
                "run": self.run_id, "pid": os.getpid()})
        except OSError:
            self._backoff()
            return False
        with self._lock:
            self._sock = sock
            self._fail_streak = 0
            self.collector = f"{addr[0]}:{addr[1]}"
            reconnected = self._ever_connected
            self._ever_connected = True
            if reconnected:
                self.reconnects += 1
        self._log("reconnect" if reconnected else "connect")
        if self.registry is not None and reconnected:
            self.registry.counter(
                "obsnet_reconnects_total", "obs_net").inc()
        return True

    def _backoff(self) -> None:
        with self._lock:
            self._fail_streak += 1
            delay = self._delays[
                min(self._fail_streak - 1, len(self._delays) - 1)]
            self._next_dial = time.monotonic() + delay

    def _drop(self, why: str) -> None:
        # close() also lands here, so the socket handoff takes the lock
        with self._lock:
            sock, self._sock = self._sock, None
            self._next_dial = time.monotonic()  # first re-dial immediate
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass
            if not self._stop.is_set():
                self._log("disconnect", why=why)

    def _ensure_connected(self) -> bool:
        if self._sock is not None:
            return True
        if self._stop.is_set() or time.monotonic() < self._next_dial:
            return False
        return self._dial()

    def _take_rows(self) -> list:
        with self._lock:
            n = min(len(self._spool), _COALESCE_ROWS)
            return [self._spool.popleft() for _ in range(n)]

    def _respool(self, rows: list) -> None:
        """Unsent rows go back to the FRONT (order preserved); whatever no
        longer fits is shed-counted — the spool bound is the bound."""
        dropped = 0
        with self._lock:
            for r in reversed(rows):
                if len(self._spool) >= self.spool_rows:
                    dropped += 1
                else:
                    self._spool.appendleft(r)
            self.shed_rows += dropped

    def _send(self, header: Dict[str, Any]) -> bool:
        sock = self._sock
        if sock is None:
            return False
        try:
            framing.send_frame(sock, header)
            return True
        except (OSError, framing.FrameError) as e:
            self._drop(f"{type(e).__name__}: {e}")
            return False

    def _run(self) -> None:
        while not self._stop.is_set() or self.spool_depth():
            if not self._ensure_connected():
                if self._stop.is_set():
                    return  # draining with no collector: spool dies with us
                self._wake.wait(0.05)
                self._wake.clear()
                continue
            now = time.monotonic()
            rows = self._take_rows()
            if rows:
                if self._send({"op": "rows", "rows": rows}):
                    self.sent_rows += len(rows)
                    self.sent_frames += 1
                else:
                    self._respool(rows)
                    continue
            if (self.registry is not None and self.snapshot_s > 0
                    and now - self._last_snap >= self.snapshot_s):
                self._last_snap = now
                if self._send({"op": "snap",
                               "metrics": self.registry.as_dict()}):
                    self.snapshots_sent += 1
            if now - self._last_stats >= _STATS_EVERY_S:
                self._last_stats = now
                self._log("stats", **self.stats())
            if not rows:
                if self._stop.is_set():
                    return
                self._wake.wait(0.05)
                self._wake.clear()

    # ------------------------------------------------------------ lifecycle
    def stats(self) -> Dict[str, Any]:
        with self._lock:
            spool_depth = len(self._spool)
            spooled, shed = self.spooled_rows, self.shed_rows
        return {"spooled_rows": spooled, "sent_rows": self.sent_rows,
                "shed_rows": shed, "spool_depth": spool_depth,
                "sent_frames": self.sent_frames,
                "snapshots_sent": self.snapshots_sent,
                "reconnects": self.reconnects,
                "connected": self._sock is not None}

    def flush(self, timeout_s: float = 5.0) -> bool:
        """Wait for the spool to drain (smoke/shutdown determinism).  True
        when fully drained in time — False never blocks the caller longer
        than the budget (telemetry's no-stall contract applies to shutdown
        too)."""
        deadline = time.monotonic() + timeout_s
        self._wake.set()
        while time.monotonic() < deadline:
            if not self.spool_depth():
                return True
            time.sleep(0.02)
        return False

    def close(self, flush_timeout_s: float = 2.0) -> None:
        """Best-effort drain, then stop.  Idempotent; never raises."""
        if self._stop.is_set():
            return
        self.flush(flush_timeout_s)
        self._stop.set()
        self._wake.set()
        self._thread.join(timeout=5)
        self._log("stats", **self.stats())
        self._drop("closed")
