"""Router federation: periodic gossiped load/version snapshots between
shared-nothing `FrontRouter`s.

N routers over one fleet coordinate through exactly two channels: the lease
files (membership + per-engine depth at lease cadence) and THIS — small UDP
datagrams carrying each router's live per-engine inflight and its rollout
target version.  With gossip, weighted least-depth dispatch stays honest
(router A sees the load router B already placed on engine 0 and stops piling
onto it) and the staleness fence stays honest (a router that never heard of
version N+1 fences against the freshest version ANY federated router knows).

UDP is the right transport for gossip: the snapshot is idempotent state, not
a command — a dropped datagram is healed by the next interval, and framing
reuses the TCP codec (one datagram = one frame, CRC-checked).  Peer
snapshots expire after ``stale_factor`` intervals, so a dead router's stale
load claims stop skewing dispatch on the monitor's own clock.

jax-free; a `gossip` JSONL row at a low cadence records peer freshness for
obs_report/obs.attribution.
"""

from __future__ import annotations

import socket
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from rainbow_iqn_apex_tpu.netcore import chaos
from rainbow_iqn_apex_tpu.serving.net import framing

# a gossip datagram is one frame; snapshots are tiny (per-engine ints), so
# anything near this bound is a protocol violation, not a big fleet
_MAX_DATAGRAM = 60_000


class RouterGossip:
    """One router's gossip endpoint: broadcast its snapshot, hold peers'.

    ``snapshot_fn`` returns this router's live view —
    ``{"inflight": {engine_id: n}, "target_version": v, "accepted": n}``
    (`FrontRouter.gossip_snapshot`).  ``peer_inflight(engine_id)`` sums the
    fresh peers' inflight for the router's dispatch weighting;
    ``peer_target_version()`` is the freshest rollout target any peer
    claims (the federated fence input).
    """

    def __init__(self, router_id: int,
                 snapshot_fn: Callable[[], Dict[str, Any]],
                 bind: Tuple[str, int] = ("127.0.0.1", 0),
                 peers: Sequence[Tuple[str, int]] = (),
                 interval_s: float = 1.0,
                 stale_factor: float = 3.0,
                 row_every: int = 5,
                 logger=None, obs_registry=None):
        self.router_id = int(router_id)
        self.snapshot_fn = snapshot_fn
        self.interval_s = float(interval_s)
        self.stale_after_s = float(stale_factor) * self.interval_s
        self.row_every = max(int(row_every), 1)
        self.logger = logger
        self.obs_registry = obs_registry
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self._sock.bind(bind)
        self._sock.settimeout(0.05)
        self.host, self.port = self._sock.getsockname()[:2]
        self._sock = chaos.maybe_wrap(self._sock, peer="gossip",
                                      logger=self.logger)
        self._peers: List[Tuple[str, int]] = [tuple(p) for p in peers]
        self._lock = threading.Lock()
        # peer router id -> (snapshot dict, monotonic rx time)
        self._view: Dict[int, Tuple[Dict[str, Any], float]] = {}
        self._seq = 0
        self.sent = 0
        self.received = 0
        self.bad_frames = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    @classmethod
    def from_config(cls, cfg, router_id: int,
                    snapshot_fn: Callable[[], Dict[str, Any]],
                    logger=None, obs_registry=None
                    ) -> Optional["RouterGossip"]:
        """None unless ``serve_net_gossip_peers`` names peers — a solo
        router needs no federation and pays nothing."""
        spec = getattr(cfg, "serve_net_gossip_peers", "") or ""
        peers = []
        for part in filter(None, (p.strip() for p in spec.split(","))):
            host, sep, port = part.rpartition(":")
            if not sep or not host or not port.isdigit():
                raise ValueError(
                    f"serve_net_gossip_peers entry {part!r} is not "
                    "host:port (e.g. \"10.0.0.1:7600,10.0.0.2:7600\"; "
                    "IPv4 or hostname only)")
            peers.append((host, int(port)))
        if not peers:
            return None
        return cls(
            router_id, snapshot_fn,
            bind=("0.0.0.0", int(cfg.serve_net_gossip_port)),
            peers=peers,
            interval_s=cfg.serve_net_gossip_interval_s,
            logger=logger, obs_registry=obs_registry)

    def set_peers(self, peers: Sequence[Tuple[str, int]]) -> None:
        with self._lock:
            self._peers = [tuple(p) for p in peers]

    # ----------------------------------------------------------------- loop
    def start(self) -> "RouterGossip":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name=f"gossip-{self.router_id}",
                daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        try:
            self._sock.close()
        except OSError:
            pass

    def _run(self) -> None:
        next_send = 0.0
        while not self._stop.is_set():
            now = time.monotonic()
            if now >= next_send:
                self.broadcast()
                next_send = now + self.interval_s
            self._drain(until=min(next_send, now + self.interval_s))

    def _drain(self, until: float) -> None:
        while not self._stop.is_set() and time.monotonic() < until:
            try:
                data, _addr = self._sock.recvfrom(_MAX_DATAGRAM)
            except socket.timeout:
                continue
            except OSError:
                return
            self._receive(data)

    # ------------------------------------------------------------- exchange
    def broadcast(self) -> int:
        """One gossip round: snapshot -> datagram -> every peer.  Returns
        peers reached (sendto errors are skipped — UDP gossip heals itself
        next interval)."""
        try:
            snap = dict(self.snapshot_fn())
        except Exception:
            return 0  # a flaky snapshot must not kill the gossip loop
        with self._lock:
            self._seq += 1
            seq = self._seq
            peers = list(self._peers)
        data = framing.encode_frame({
            "op": "gossip", "router": self.router_id, "seq": seq,
            "ts": round(time.time(), 3), "snap": snap,
        })
        reached = 0
        for peer in peers:
            try:
                self._sock.sendto(data, peer)
                reached += 1
            except OSError:
                pass
        with self._lock:
            self.sent += 1
            emit = self.sent % self.row_every == 0
        if emit:
            self._emit_row()
        return reached

    def _receive(self, data: bytes) -> None:
        try:
            frames = framing.FrameReader(_MAX_DATAGRAM).feed(data)
        except framing.FrameError:
            with self._lock:
                self.bad_frames += 1
            return
        for header, _blob in frames:
            if header.get("op") != "gossip":
                continue
            peer_id = header.get("router")
            if peer_id is None or int(peer_id) == self.router_id:
                continue  # self-echo (a peer list naming ourselves)
            now = time.monotonic()
            with self._lock:
                prev = self._view.get(int(peer_id))
                # out-of-order datagrams: keep the newest seq only — but a
                # seq LOWER than a STALE entry's is a restarted peer whose
                # counter reset, not reordering; refusing it would deafen
                # this router to the peer until its new seq caught up
                if (prev is not None
                        and now - prev[1] <= self.stale_after_s
                        and prev[0].get("_seq", -1) >= int(
                            header.get("seq", 0))):
                    continue
                snap = dict(header.get("snap") or {})
                snap["_seq"] = int(header.get("seq", 0))
                self._view[int(peer_id)] = (snap, now)
                self.received += 1

    def poll_once(self, budget_s: float = 0.2) -> None:
        """Drain pending datagrams inline (thread-less mode for tests and
        single-threaded harnesses)."""
        self._drain(until=time.monotonic() + float(budget_s))

    # ----------------------------------------------------------------- reads
    def _fresh_view(self) -> Dict[int, Dict[str, Any]]:
        now = time.monotonic()
        with self._lock:
            return {pid: snap for pid, (snap, t_rx) in self._view.items()
                    if now - t_rx <= self.stale_after_s}

    def peer_inflight(self, engine_id: int) -> int:
        """Load other routers currently have in flight on ``engine_id`` —
        the federation term in weighted least-depth dispatch."""
        total = 0
        for snap in self._fresh_view().values():
            total += int((snap.get("inflight") or {}).get(
                str(int(engine_id)), 0))
        return total

    def peer_target_version(self) -> int:
        """The freshest rollout target any fresh peer claims (0 when no
        peer is fresh) — max() this with the local target so a router that
        missed a publish still fences engines against the fleet's truth."""
        return max((int(snap.get("target_version", 0))
                    for snap in self._fresh_view().values()), default=0)

    def peers_fresh(self) -> int:
        return len(self._fresh_view())

    # ------------------------------------------------------------------- obs
    def _emit_row(self) -> None:
        fresh = self.peers_fresh()
        with self._lock:
            known = len(self._view)
            n_peers = len(self._peers)
        if self.obs_registry is not None:
            self.obs_registry.gauge("gossip_peers_fresh", "router").set(fresh)
        if self.logger is not None:
            try:
                self.logger.log(
                    "gossip", router=self.router_id, peers=n_peers,
                    fresh=fresh, stale=known - fresh, sent=self.sent,
                    received=self.received, bad_frames=self.bad_frames)
            except Exception:
                pass
