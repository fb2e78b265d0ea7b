"""Cross-host serving plane (serving/net/): frame-codec hardening (torn
reads, oversize rejection, checksum trailer), the RemoteTransport <->
TransportServer loop over real loopback sockets, lease-driven remote
discovery with BOUNDED liveness probes, router federation via UDP gossip,
wire weight rollouts (int8-delta, backward refusal at both ends, bit-exact
digests), and the obs folding (net/gossip rows -> schema/lint/RunHealth/
obs_report/health_attribution).  Everything here is jax-free: engines are protocol
fakes driving the REAL sockets — `make net-smoke` runs the multi-process
fleet against real PolicyServers on top."""

import json
import socket
import threading
import time

import numpy as np
import pytest

from rainbow_iqn_apex_tpu.parallel.elastic import HeartbeatWriter
from rainbow_iqn_apex_tpu.serving.batcher import (
    ServeFuture,
    ServerClosed,
    ServerOverloaded,
)
from rainbow_iqn_apex_tpu.serving.fleet import (
    EngineRegistry,
    FleetRollout,
    FrontRouter,
)
from rainbow_iqn_apex_tpu.serving.fleet.registry import EngineDead
from rainbow_iqn_apex_tpu.serving.net import (
    RemoteEngine,
    RemoteTransport,
    RouterGossip,
    TransportServer,
    framing,
)
from rainbow_iqn_apex_tpu.utils import quantize
from rainbow_iqn_apex_tpu.utils.faults import RetryPolicy

pytestmark = pytest.mark.net

OBS = np.zeros((4, 4, 2), np.uint8)


# ---------------------------------------------------------------- fakes
class FakeServer:
    """try_submit/depth protocol fake: the test fulfils (`pump`) or kills
    queued futures deterministically — the engine side of the wire without
    jax."""

    def __init__(self, cap=64):
        self.cap = cap
        self.q = []
        self.lock = threading.Lock()

    def try_submit(self, obs):
        with self.lock:
            if len(self.q) >= self.cap:
                return None
            fut = ServeFuture(np.asarray(obs))
            self.q.append(fut)
            return fut

    def depth(self):
        with self.lock:
            return len(self.q)

    def pump(self, action=3):
        with self.lock:
            q, self.q = self.q, []
        served = 0
        for fut in q:
            if not fut.cancelled():
                fut.set_result(action, np.arange(4, dtype=np.float32))
                served += 1
        return served

    def abort(self):
        with self.lock:
            q, self.q = self.q, []
        for fut in q:
            fut.set_error(ServerClosed("engine killed"))


class FakeLocalTransport:
    def __init__(self):
        self.lanes, self.buckets, self._v = 2, (4, 8), 0

    def version(self):
        return self._v

    def set_version(self, v):
        self._v = int(v)


class FakeWriter:
    def __init__(self, hb=None):
        self.hb = hb
        self.payload = {}

    def update_payload(self, **kw):
        self.payload.update(kw)
        if self.hb is not None:
            self.hb.update_payload(**kw)

    def set_weight_version(self, v):
        self.update_payload(weight_version=int(v))


class FakeEngine:
    """FleetEngine protocol fake with the REAL DeltaDecoder and the real
    monotonicity guard, so wire rollouts exercise genuine codec state."""

    def __init__(self, server, hb=None):
        self.server = server
        self.writer = FakeWriter(hb)
        self.transport = FakeLocalTransport()
        self._dec = quantize.DeltaDecoder()
        self.served_digest = None
        self.adopts = 0

    def _refuse_backward(self, version):
        if version <= self.transport.version() and self.transport.version() > 0:
            raise ValueError(f"refusing backward rollout {version}")

    def adopt(self, params, version):
        self._refuse_backward(version)
        self.transport.set_version(version)
        self.served_digest = quantize.tree_digest(params)
        self.adopts += 1
        return version

    def adopt_packet(self, packet):
        self._refuse_backward(packet.version)
        params = self._dec.apply(packet)
        self.transport.set_version(packet.version)
        self.served_digest = quantize.tree_digest(params)
        self.adopts += 1
        return packet.version

    def adopt_chain(self, packets):
        params = self._dec.apply_chain(list(packets))
        if self._dec.version > self.transport.version():
            self.transport.set_version(self._dec.version)
            self.served_digest = quantize.tree_digest(params)
            self.adopts += 1
        return self._dec.version


def wire_pair(server=None, engine=None, **client_kw):
    """One TransportServer + connected RemoteTransport over loopback."""
    server = server or FakeServer()
    engine = engine if engine is not None else FakeEngine(server)
    ts = TransportServer(server, engine=engine, port=0).start()
    rt = RemoteTransport("127.0.0.1", ts.port, engine_id=1, **client_kw)
    return server, engine, ts, rt


def tiny_tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"a": {"w": rng.standard_normal((6, 4)).astype(np.float32)},
            "b": rng.standard_normal(5).astype(np.float32)}


# ---------------------------------------------------------- frame codec
def test_frame_roundtrip_and_torn_reads():
    frame = framing.encode_frame({"op": "x", "rid": 7}, b"payload")
    reader = framing.FrameReader()
    got = []
    for i in range(len(frame)):  # worst-case dribble: one byte at a time
        got += reader.feed(frame[i:i + 1])
    assert got == [({"op": "x", "rid": 7}, b"payload")]
    # two frames in one feed + a partial third stays buffered
    f2 = framing.encode_frame({"n": 2})
    got = reader.feed(frame + f2 + frame[:5])
    assert [h for h, _ in got] == [{"op": "x", "rid": 7}, {"n": 2}]
    assert reader.pending_bytes() == 5

    # a blocking socket pair with dribbled writes: recv_frame reassembles
    a, b = socket.socketpair()
    try:
        def dribble():
            for i in range(0, len(frame), 3):
                a.sendall(frame[i:i + 3])
                time.sleep(0.001)
        t = threading.Thread(target=dribble)
        t.start()
        header, blob = framing.recv_frame(b)
        t.join()
        assert header == {"op": "x", "rid": 7} and blob == b"payload"
        # EOF mid-frame (peer died half-sent) is a TORN frame, not a clean end
        a.sendall(frame[:9])
        a.close()
        with pytest.raises(framing.FrameTruncated):
            framing.recv_frame(b)
    finally:
        b.close()


def test_frame_oversize_rejected_with_reason():
    frame = framing.encode_frame({"op": "big"}, b"z" * 1000)
    with pytest.raises(framing.FrameTooLarge) as ei:
        framing.FrameReader(max_frame_bytes=100).feed(frame)
    # the error must carry the declared size, the bound, and the knob
    msg = str(ei.value)
    assert "100-byte bound" in msg and "serve_net_max_frame_mb" in msg
    # blocking path rejects too, BEFORE reading the body
    a, b = socket.socketpair()
    try:
        a.sendall(frame)
        with pytest.raises(framing.FrameTooLarge):
            framing.recv_frame(b, max_frame_bytes=100)
    finally:
        a.close()
        b.close()


def test_frame_checksum_and_protocol_errors():
    frame = bytearray(framing.encode_frame({"op": "x"}, b"data"))
    frame[len(frame) // 2] ^= 0xFF  # flip one payload bit
    with pytest.raises(framing.FrameCorrupt):
        framing.FrameReader().feed(bytes(frame))
    # wrong magic: a peer speaking something else entirely (e.g. HTTP)
    with pytest.raises(framing.FrameProtocol):
        framing.FrameReader().feed(b"GET / HTTP/1.1\r\n\r\n")


def test_frame_reader_fuzz_never_lies_and_never_explodes():
    """Seeded fuzz hardening (ISSUE 19 satellite): random byte flips,
    truncations, duplications, and junk splices over valid frame streams
    must ALWAYS land as a typed Frame* error (after which the caller
    resyncs by reconnecting — a fresh reader) or as frames that decode
    byte-identical to ones actually sent.  Never an unhandled exception,
    never a silently-wrong payload — the CRC is the witness."""
    rng = np.random.default_rng(1905)
    originals = []
    for i in range(24):
        blob = rng.integers(0, 256, int(rng.integers(0, 400)),
                            dtype=np.uint8).tobytes()
        originals.append((({"op": "fuzz", "rid": i}), blob))
    clean = b"".join(framing.encode_frame(h, b) for h, b in originals)
    sent = {(json.dumps(h, sort_keys=True), b) for h, b in originals}

    def mutate(stream, rng):
        stream = bytearray(stream)
        kind = rng.integers(0, 4)
        if kind == 0 and stream:  # flip a byte
            i = int(rng.integers(0, len(stream)))
            stream[i] ^= int(rng.integers(1, 256))
        elif kind == 1 and stream:  # truncate (peer died mid-write)
            del stream[int(rng.integers(0, len(stream))):]
        elif kind == 2 and stream:  # duplicate a slice (retransmit bug)
            i = int(rng.integers(0, len(stream)))
            j = int(rng.integers(i, min(i + 64, len(stream)) + 1))
            stream[i:i] = stream[i:j]
        else:  # splice in junk (a foreign protocol burst)
            i = int(rng.integers(0, len(stream) + 1))
            junk = rng.integers(0, 256, int(rng.integers(1, 32)),
                                dtype=np.uint8).tobytes()
            stream[i:i] = junk
        return bytes(stream)

    for trial in range(200):
        stream = clean
        for _ in range(int(rng.integers(1, 4))):
            stream = mutate(stream, rng)
        reader = framing.FrameReader()
        decoded, pos = [], 0
        while pos < len(stream):
            step = int(rng.integers(1, 4096))
            chunk = stream[pos:pos + step]
            pos += step
            try:
                decoded += reader.feed(chunk)
            except framing.FrameError:
                break  # typed: the plane drops the conn and reconnects
            except Exception as e:  # pragma: no cover - the failure mode
                raise AssertionError(
                    f"trial {trial}: unhandled {type(e).__name__}: {e}")
        for header, blob in decoded:
            key = (json.dumps(header, sort_keys=True), blob)
            assert key in sent, (
                f"trial {trial}: decoded a frame nobody sent (CRC lied)")


def test_ndarray_and_blob_sequence_codecs():
    arr = np.random.default_rng(0).integers(0, 255, (3, 4, 2), dtype=np.uint8)
    meta, blob = framing.encode_ndarray(arr)
    assert (framing.decode_ndarray(meta, blob) == arr).all()
    with pytest.raises(framing.FrameCorrupt):
        framing.decode_ndarray(meta, blob[:-1])  # size mismatch
    blobs = [b"a", b"", b"ccc"]
    assert framing.unpack_blobs(framing.pack_blobs(blobs)) == blobs
    with pytest.raises(framing.FrameCorrupt):
        framing.unpack_blobs(framing.pack_blobs(blobs)[:-1])


def test_packet_wire_roundtrip_bit_exact():
    tree = tiny_tree()
    enc = quantize.DeltaEncoder(base_interval=4)
    base = enc.encode(tree, 1)
    delta = enc.encode({"a": {"w": tree["a"]["w"] + 0.02}, "b": tree["b"]}, 2)
    dec = quantize.DeltaDecoder()
    for p in (base, delta):
        wire = quantize.packet_from_bytes(quantize.packet_to_bytes(p))
        assert (wire.kind, wire.version, wire.prev_version) == (
            p.kind, p.version, p.prev_version)
        dec.apply(wire)
    # decoding the WIRE copies lands bit-exact on the encoder's closed loop
    assert quantize.tree_digest(dec.params()) == quantize.tree_digest(
        enc.reconstructed())


# ------------------------------------------------------ transport <-> server
def test_remote_submit_result_and_piggybacked_state():
    server, _engine, ts, rt = wire_pair()
    try:
        fut = rt.submit(OBS)
        assert rt.depth() >= 1  # ack piggybacked the live queue depth
        server.pump(action=5)
        action, q = fut.result(timeout=5)
        assert action == 5 and q.shape == (4,)
        assert rt.lanes == 2 and rt.buckets == (4, 8)
    finally:
        ts.stop()
        rt.close()


def test_remote_shed_raises_overloaded_synchronously():
    server, _e, ts, rt = wire_pair(server=FakeServer(cap=2))
    try:
        futs = [rt.submit(OBS) for _ in range(2)]
        with pytest.raises(ServerOverloaded):
            rt.submit(OBS)  # the shed travels back in the ack, one RTT
        server.pump()
        for f in futs:
            f.result(timeout=5)
    finally:
        ts.stop()
        rt.close()


def test_connection_drop_fails_inflight_as_engine_dead():
    server, _e, ts, rt = wire_pair()
    fut = rt.submit(OBS)
    ts.stop()  # the wire analog of SIGKILL: no goodbye frame
    with pytest.raises(EngineDead):
        fut.result(timeout=5)
    # subsequent submits fail fast (bounded dial, not a hang)
    t0 = time.monotonic()
    with pytest.raises(EngineDead):
        rt.submit(OBS)
    assert time.monotonic() - t0 < 2.0
    rt.close()
    server.abort()


def test_cancel_propagates_to_engine_side():
    server, _e, ts, rt = wire_pair()
    try:
        fut = rt.submit(OBS)
        assert fut.cancel()
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            # the engine-side future should see the cancel and be skipped
            with server.lock:
                cancelled = server.q and server.q[0].cancelled()
            if cancelled:
                break
            time.sleep(0.01)
        assert cancelled
        assert server.pump() == 0  # no slot burned for the abandoned request
    finally:
        ts.stop()
        rt.close()


def test_reconnect_with_backoff_after_engine_restart():
    server, engine, ts, rt = wire_pair(
        retry=RetryPolicy(attempts=4, base_delay_s=0.05, max_delay_s=0.2))
    port = ts.port
    try:
        assert rt.probe() is not None
        ts.stop()
        time.sleep(0.1)
        assert rt.probe() is None  # down: bounded failure, not a hang
        # restart the engine on the SAME port (the respawned-host shape)
        ts2 = TransportServer(server, engine=engine, port=port).start()
        deadline = time.monotonic() + 5.0
        back = False
        while time.monotonic() < deadline:
            if rt.probe() is not None:
                back = True
                break
            time.sleep(0.05)
        assert back, "transport never re-dialed a revived engine"
        assert rt.reconnects >= 1
        ts2.stop()
    finally:
        ts.stop()
        rt.close()


def test_bounded_probe_against_hung_remote():
    """A remote that ACCEPTS the connection but never answers (wedged
    process, half-dead host) must cost the prober its budget, never a
    stall — the satellite guarantee the registry sweep relies on."""
    hung = socket.socket()
    hung.bind(("127.0.0.1", 0))
    hung.listen(1)
    rt = RemoteTransport("127.0.0.1", hung.getsockname()[1],
                         probe_timeout_s=0.2)
    try:
        t0 = time.monotonic()
        assert rt.probe() is None
        assert time.monotonic() - t0 < 1.0
        assert rt.probe_timeouts == 1
    finally:
        rt.close()
        hung.close()


# --------------------------------------------------- registry + discovery
def test_registry_discovers_remote_engine_from_lease(tmp_path):
    hb_dir = str(tmp_path / "hb")
    server = FakeServer()
    hb = HeartbeatWriter(hb_dir, 3, 0.05, role="engine")
    engine = FakeEngine(server, hb=hb)
    ts = TransportServer.for_engine(engine, port=0)
    assert hb.payload["addr"] == "127.0.0.1" and hb.payload["port"] == ts.port
    ts.start()
    hb.start()
    time.sleep(0.1)
    built = []

    def factory(lease):
        rt = RemoteTransport(lease.addr, lease.port, engine_id=lease.host,
                             connect=False)
        built.append(rt)
        return rt

    registry = EngineRegistry(hb_dir, lease_timeout_s=2.0,
                              transport_factory=factory,
                              probe_interval_s=0.0)
    try:
        events = registry.poll()
        assert {"event": "engine_alive", "engine": 3, "epoch": 0} in events
        handle = registry.get(3)
        assert handle is not None and handle.routable
        assert built and handle.transport is built[0]
        # the discovered transport really dispatches
        fut = handle.transport.submit(OBS)
        server.pump()
        assert fut.result(timeout=5)[0] == 3
    finally:
        hb.stop()
        ts.stop()
        for rt in built:
            rt.close()


def test_registry_probe_eviction_is_bounded_and_sticky(tmp_path):
    """A hung remote is marked unroutable within the probe bound; the scan
    over it never stalls, and the still-fresh lease alone does not revive
    it (mark_dead stickiness, probe edition)."""
    hb_dir = str(tmp_path / "hb")
    hung = socket.socket()
    hung.bind(("127.0.0.1", 0))
    hung.listen(1)
    hb = HeartbeatWriter(hb_dir, 4, 0.05, role="engine")
    hb.update_payload(addr="127.0.0.1", port=hung.getsockname()[1])
    hb.start()
    time.sleep(0.1)
    registry = EngineRegistry(
        hb_dir, lease_timeout_s=5.0,
        transport_factory=lambda lease: RemoteTransport(
            lease.addr, lease.port, engine_id=lease.host, connect=False),
        probe_timeout_s=0.2, probe_interval_s=0.0)
    try:
        registry.poll()  # discover + first probe (hangs -> bounded timeout)
        t0 = time.monotonic()
        registry.poll()
        assert time.monotonic() - t0 < 2.0  # the sweep stayed bounded
        handle = registry.get(4)
        assert handle is not None and not handle.routable
        assert handle.suspect_since is not None and handle.suspect_probe
        # probe suspicion must survive CONTINUING heartbeats: the wedged
        # engine's process is alive and beating, and with probes paused
        # (large interval) the fresh beats alone must not flap it back in
        registry.probe_interval_s = 1e9
        time.sleep(0.15)  # several beats written after the observation
        registry.poll()
        handle = registry.get(4)
        assert not handle.routable and handle.suspect_since is not None
    finally:
        hb.stop()
        hung.close()
        handle = registry.get(4)
        if handle is not None and handle.transport is not None:
            handle.transport.close()


def test_registry_rebuilds_transport_when_lease_endpoint_moves(tmp_path):
    """A respawned engine host advertises a NEW ephemeral port in its
    fresh lease; the registry must replace the old transport (which would
    dial the dead port forever — and probe suspicion, which only a good
    probe clears, would fence the healthy respawn out permanently)."""
    hb_dir = str(tmp_path / "hb")
    server = FakeServer()
    hb = HeartbeatWriter(hb_dir, 6, 10.0, role="engine")
    engine = FakeEngine(server, hb=hb)
    ts1 = TransportServer.for_engine(engine, port=0)
    ts1.start()
    hb.beat()
    built = []

    def factory(lease):
        rt = RemoteTransport(lease.addr, lease.port, engine_id=lease.host,
                             probe_timeout_s=0.2, connect=False)
        built.append(rt)
        return rt

    registry = EngineRegistry(hb_dir, lease_timeout_s=30.0,
                              transport_factory=factory,
                              probe_timeout_s=0.2, probe_interval_s=0.0)
    try:
        registry.poll()
        assert len(built) == 1 and built[0].port == ts1.port
        # the host dies and respawns on a NEW port; its fresh lease says so
        ts1.stop()
        registry.poll()  # probe fails against the dead port -> suspect
        assert not registry.get(6).routable
        ts2 = TransportServer.for_engine(engine, port=0)
        assert ts2.port != ts1.port
        ts2.start()
        hb.beat()  # fresh lease now advertises the new endpoint
        registry.poll()
        handle = registry.get(6)
        assert len(built) == 2 and handle.transport is built[1]
        assert handle.transport.port == ts2.port
        assert handle.routable  # suspicion reset with the new endpoint
        fut = handle.transport.submit(OBS)
        server.pump()
        assert fut.result(timeout=5)[0] == 3
        ts2.stop()
    finally:
        hb.stop()
        ts1.stop()
        for rt in built:
            rt.close()


def test_registry_emits_net_stats_rows(tmp_path):
    class Rows:
        def __init__(self):
            self.rows = []

        def log(self, kind, **fields):
            self.rows.append({"kind": kind, **fields})

    hb_dir = str(tmp_path / "hb")
    server = FakeServer()
    hb = HeartbeatWriter(hb_dir, 5, 0.05, role="engine")
    engine = FakeEngine(server, hb=hb)
    ts = TransportServer.for_engine(engine, port=0).start()
    hb.start()
    time.sleep(0.1)
    rows = Rows()
    registry = EngineRegistry(
        hb_dir, lease_timeout_s=2.0, logger=rows,
        transport_factory=lambda lease: RemoteTransport(
            lease.addr, lease.port, engine_id=lease.host, connect=False),
        probe_interval_s=0.0, net_stats_interval_s=0.01)
    try:
        registry.poll()
        registry._t_net_stats = 0.0
        registry.poll()
        stats = [r for r in rows.rows
                 if r["kind"] == "net" and r.get("event") == "stats"]
        assert stats, rows.rows
        snap = stats[-1]
        assert snap["engine"] == 5 and snap["peer"].startswith("127.0.0.1:")
        assert {"rtt_ms", "reconnects", "bytes_sent",
                "bytes_recv"} <= set(snap)
    finally:
        hb.stop()
        ts.stop()
        h = registry.get(5)
        if h is not None and h.transport is not None:
            h.transport.close()


# ------------------------------------------------------------- federation
def test_gossip_exchange_staleness_and_self_echo():
    a_snap = {"inflight": {"1": 4}, "target_version": 9}
    ga = RouterGossip(0, lambda: a_snap, interval_s=1.0)
    gb = RouterGossip(1, lambda: {"inflight": {}, "target_version": 2},
                      interval_s=1.0)
    try:
        # peer lists INCLUDING ourselves: the self-echo must be dropped
        ga.set_peers([("127.0.0.1", gb.port), ("127.0.0.1", ga.port)])
        gb.set_peers([("127.0.0.1", ga.port)])
        ga.broadcast()
        gb.broadcast()
        ga.poll_once(0.3)
        gb.poll_once(0.3)
        assert gb.peer_inflight(1) == 4
        assert gb.peer_target_version() == 9
        assert ga.peer_target_version() == 2
        assert 0 not in ga._view  # no self-snapshot
        # staleness: a dead router's claims expire on the clock
        gb.stale_after_s = 0.0
        time.sleep(0.02)
        assert gb.peer_inflight(1) == 0 and gb.peers_fresh() == 0
    finally:
        ga.stop()
        gb.stop()


def test_router_dispatch_weighs_gossiped_peer_load():
    """Two engines, equal local depth; a peer router gossips 10 in flight on
    engine 0 — dispatch must pick engine 1 (the federation keeping
    least-depth honest without shared state)."""
    s0, s1 = FakeServer(), FakeServer()
    e0, e1 = FakeEngine(s0), FakeEngine(s1)
    ts0 = TransportServer(s0, engine=e0, port=0).start()
    ts1 = TransportServer(s1, engine=e1, port=0).start()
    rt0 = RemoteTransport("127.0.0.1", ts0.port, engine_id=0)
    rt1 = RemoteTransport("127.0.0.1", ts1.port, engine_id=1)
    registry = EngineRegistry()
    registry.attach(0, rt0)
    registry.attach(1, rt1)
    peer_load = {0: 10, 1: 0}
    router = FrontRouter(registry,
                         peer_inflight_fn=lambda eid: peer_load[eid])
    try:
        rf = router.submit(OBS)
        assert s1.depth() == 1 and s0.depth() == 0
        s1.pump()
        rf.result(timeout=5)
        # flip the gossiped load: dispatch flips with it
        peer_load.update({0: 0, 1: 10})
        rf = router.submit(OBS)
        assert s0.depth() == 1
        s0.pump()
        rf.result(timeout=5)
    finally:
        router.stop()
        ts0.stop()
        ts1.stop()
        rt0.close()
        rt1.close()


def test_gossip_accepts_restarted_peer_with_reset_seq():
    """A peer router that restarts resets its seq counter; once the stored
    snapshot is STALE, a lower seq must be accepted (it is a new
    incarnation, not reordering) — refusing it would deafen this router
    to the peer for ~old_seq intervals."""
    gb = RouterGossip(1, lambda: {}, interval_s=1.0)
    try:
        frame = framing.encode_frame({
            "op": "gossip", "router": 0, "seq": 1000,
            "snap": {"inflight": {"1": 7}, "target_version": 5}})
        gb._receive(frame)
        assert gb.peer_inflight(1) == 7
        # in-window reordering with a FRESH entry is still dropped
        stale_frame = framing.encode_frame({
            "op": "gossip", "router": 0, "seq": 999,
            "snap": {"inflight": {"1": 1}, "target_version": 5}})
        gb._receive(stale_frame)
        assert gb.peer_inflight(1) == 7
        # expire the entry, then the restarted peer's seq=1 must land
        gb.stale_after_s = 0.0
        time.sleep(0.01)
        restart = framing.encode_frame({
            "op": "gossip", "router": 0, "seq": 1,
            "snap": {"inflight": {"1": 2}, "target_version": 6}})
        gb._receive(restart)
        gb.stale_after_s = 3.0
        assert gb.peer_inflight(1) == 2
        assert gb.peer_target_version() == 6
    finally:
        gb.stop()


def test_router_target_version_federates_peer_claim():
    """A router that missed a publish still fences against the freshest
    target any peer gossips (peer_target_fn joins via max)."""
    registry = EngineRegistry()
    peer_target = [0]
    router = FrontRouter(registry, peer_target_fn=lambda: peer_target[0])
    try:
        assert router.target_version() == 0
        peer_target[0] = 7  # a peer saw version 7 published
        assert router.target_version() == 7
        # an explicit local target still wins when fresher
        router._target_version_fn = lambda: 9
        assert router.target_version() == 9
        # the SNAPSHOT broadcasts the LOCAL target only: re-broadcasting
        # the federated max would echo a stale high claim between routers
        # forever, past any gossip staleness expiry
        router._target_version_fn = lambda: 3
        assert router.gossip_snapshot()["target_version"] == 3
        assert router.target_version() == 7  # reads still federate
    finally:
        router.stop()


def test_from_config_seams_are_the_on_switch(tmp_path):
    """serve_net_* unset -> both from_config seams return None (in-process
    fleet untouched); set -> a real listener / gossip endpoint."""
    from rainbow_iqn_apex_tpu.config import Config

    server = FakeServer()
    hb = HeartbeatWriter(str(tmp_path / "hb"), 2, 10.0, role="engine")
    engine = FakeEngine(server, hb=hb)
    off = Config()
    assert TransportServer.from_config(off, engine) is None
    assert RouterGossip.from_config(off, 0, lambda: {}) is None
    on = Config(serve_net_host="127.0.0.1", serve_net_max_frame_mb=1,
                serve_net_gossip_peers="127.0.0.1:19999")
    ts = TransportServer.from_config(on, engine)
    try:
        assert ts is not None and ts.port > 0
        assert ts.max_frame_bytes == 1 << 20
        assert engine.writer.payload["addr"] == "127.0.0.1"
        assert engine.writer.payload["port"] == ts.port
    finally:
        ts.stop()
    gossip = RouterGossip.from_config(on, 0, lambda: {})
    try:
        assert gossip is not None
        assert gossip._peers == [("127.0.0.1", 19999)]
    finally:
        gossip.stop()
    # a malformed peer entry fails with a REASONED error naming the entry
    with pytest.raises(ValueError, match="10.0.0.1"):
        RouterGossip.from_config(
            Config(serve_net_gossip_peers="10.0.0.1"), 0, lambda: {})


def test_probe_unreachable_is_not_a_probe_timeout():
    """Connection-refused probes must NOT emit probe_timeout rows — the
    RUNBOOK triage keys probe_timeout to 'wedged engine behind a fresh
    lease', and a dead host's signature is the disconnect + lease expiry."""
    class Rows:
        def __init__(self):
            self.rows = []

        def log(self, kind, **fields):
            self.rows.append({"kind": kind, **fields})

    rows = Rows()
    # nothing listens here: every dial is refused
    dead = socket.socket()
    dead.bind(("127.0.0.1", 0))
    port = dead.getsockname()[1]
    dead.close()
    rt = RemoteTransport("127.0.0.1", port, probe_timeout_s=0.2,
                         logger=rows, connect=False)
    try:
        assert rt.probe() is None
        assert rt.probe_timeouts == 0
        assert not [r for r in rows.rows
                    if r.get("event") == "probe_timeout"]
    finally:
        rt.close()


def test_router_gossip_snapshot_shape():
    registry = EngineRegistry()
    router = FrontRouter(registry)
    snap = router.gossip_snapshot()
    assert set(snap) == {"inflight", "target_version", "accepted"}
    router.stop()


# ---------------------------------------------------------- wire rollouts
def test_wire_rollout_delta_chain_and_late_joiner():
    tree = tiny_tree()
    s0, s1 = FakeServer(), FakeServer()
    e0, e1 = FakeEngine(s0), FakeEngine(s1)
    ts0 = TransportServer(s0, engine=e0, port=0).start()
    ts1 = TransportServer(s1, engine=e1, port=0).start()
    rt0 = RemoteTransport("127.0.0.1", ts0.port, engine_id=0)
    rollout = FleetRollout(compression="int8_delta", base_interval=4)
    rollout.track(RemoteEngine(0, rt0))
    try:
        rollout.publish(tree, version=1)  # base over the wire
        rollout.publish({"a": {"w": tree["a"]["w"] + 0.03},
                         "b": tree["b"]}, version=2)  # delta over the wire
        target = rollout.reconstructed_digest()
        assert e0.served_digest == target and rt0.version() == 2
        # late joiner: discovered after two publishes, caught up via the
        # chain-from-base — lands bit-exact without a re-publish
        rt1 = RemoteTransport("127.0.0.1", ts1.port, engine_id=1)
        rollout.track(RemoteEngine(1, rt1))
        assert rollout.sync() == 1
        assert e1.served_digest == target
        assert rollout.converged()
        rt1.close()
    finally:
        ts0.stop()
        ts1.stop()
        rt0.close()


def test_wire_rollout_backward_refused_at_both_ends():
    tree = tiny_tree()
    server = FakeServer()
    engine = FakeEngine(server)
    ts = TransportServer(server, engine=engine, port=0).start()
    rt = RemoteTransport("127.0.0.1", ts.port, engine_id=0)
    remote = RemoteEngine(0, rt)
    rollout = FleetRollout(compression="off")
    rollout.track(remote)
    try:
        rollout.publish(tree, version=3)
        assert engine.adopts == 1
        # controller layer refuses without ever touching the wire
        refused = rollout.publish(tree, version=2)
        assert refused["event"] == "refused_backward"
        assert engine.adopts == 1
        # engine layer refuses too when the controller check is bypassed:
        # the ValueError travels back over the socket as a ValueError
        with pytest.raises(ValueError):
            remote.adopt(tree, 1)
        assert engine.transport.version() == 3
    finally:
        ts.stop()
        rt.close()


def test_wire_uncompressed_adopt_is_bit_exact():
    tree = tiny_tree(seed=9)
    server = FakeServer()
    engine = FakeEngine(server)
    ts = TransportServer(server, engine=engine, port=0).start()
    rt = RemoteTransport("127.0.0.1", ts.port, engine_id=0)
    try:
        RemoteEngine(0, rt).adopt(tree, 1)
        assert engine.served_digest == quantize.tree_digest(tree)
        assert RemoteEngine(0, rt).served_digest(timeout_s=2.0) == \
            quantize.tree_digest(tree)
    finally:
        ts.stop()
        rt.close()


def test_wire_chain_gap_surfaces_as_chain_broken():
    tree = tiny_tree()
    server = FakeServer()
    engine = FakeEngine(server)
    ts = TransportServer(server, engine=engine, port=0).start()
    rt = RemoteTransport("127.0.0.1", ts.port, engine_id=0)
    enc = quantize.DeltaEncoder(base_interval=10)
    enc.encode(tree, 1)
    delta = enc.encode({"a": {"w": tree["a"]["w"] + 0.01}, "b": tree["b"]}, 2)
    try:
        with pytest.raises(quantize.DeltaChainBroken):
            RemoteEngine(0, rt).adopt_packet(delta)  # no base held remotely
        # sync()'s repair path: the chain-from-base replays clean
        assert RemoteEngine(0, rt).adopt_chain(enc.chain()) == 2
        assert engine.served_digest == quantize.tree_digest(
            enc.reconstructed())
    finally:
        ts.stop()
        rt.close()


# ------------------------------------------------------------- obs folding
def test_net_and_gossip_rows_validate_and_lint():
    import os
    import sys

    from rainbow_iqn_apex_tpu.obs.schema import validate_row

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from scripts.lint_jsonl import lint_line

    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    import json
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "m.jsonl")
        logger = MetricsLogger(path, run_id="t", echo=False)
        logger.log("net", event="stats", peer="127.0.0.1:9", engine=1,
                   rtt_ms=0.4, reconnects=0, bytes_sent=10, bytes_recv=20)
        logger.log("net", event="disconnect", peer="127.0.0.1:9", engine=1)
        logger.log("gossip", router=0, peers=1, fresh=1, stale=0, sent=5,
                   received=5, bad_frames=0)
        logger.close()
        with open(path) as fh:
            lines = fh.read().splitlines()
        assert len(lines) == 3
        for line in lines:
            assert lint_line(line) is None, line
            assert validate_row(json.loads(line)) == []
        # a net row WITHOUT its required key fails validation
        bad = dict(json.loads(lines[0]))
        del bad["event"]
        assert validate_row(bad)


def test_runhealth_folds_reconnect_storm_as_degraded():
    from rainbow_iqn_apex_tpu.obs.health import RunHealth
    from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry

    health = RunHealth(MetricRegistry())
    assert health.status() == "ok"
    base = {"kind": "net", "peer": "127.0.0.1:9", "engine": 1}
    health.observe_row({**base, "event": "stats"})
    assert health.status() == "ok"  # stats rows are not flaps
    health.observe_row({**base, "event": "disconnect"})
    assert health.status() == "degraded"
    row = health.tick(step=1)
    assert row["status"] == "degraded"
    # window reset: a quiet window heals
    assert health.tick(step=2)["status"] == "ok"
    # a storm holds it degraded window after window
    for _ in range(3):
        health.observe_row({**base, "event": "reconnect"})
    assert health.tick(step=3)["status"] == "degraded"
    # gossip rows never degrade (visibility only)
    health.observe_row({"kind": "gossip", "peers": 2, "fresh": 0, "stale": 2})
    assert health.tick(step=4)["status"] == "ok"


def test_obs_report_net_section_and_health_attribution_tally(tmp_path):
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from scripts.obs_report import aggregate, render

    from rainbow_iqn_apex_tpu.obs.attribution import health_attribution
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    path = str(tmp_path / "metrics.jsonl")
    logger = MetricsLogger(path, run_id="t", echo=False)
    logger.log("net", event="connect", peer="127.0.0.1:7001", engine=1)
    logger.log("net", event="stats", peer="127.0.0.1:7001", engine=1,
               rtt_ms=0.8, reconnects=2, probe_timeouts=1,
               bytes_sent=1234, bytes_recv=567, connected=True)
    logger.log("net", event="disconnect", peer="127.0.0.1:7001", engine=1)
    logger.log("gossip", router=0, peers=2, fresh=1, stale=1, sent=9,
               received=4, bad_frames=0)
    logger.close()
    with open(path) as fh:
        import json
        rows = [json.loads(line) for line in fh]
    report = aggregate(rows)
    net = report["net"]
    assert net["flaps"] == 1 and net["gossip_fresh"] == 1
    peer = net["peers"]["127.0.0.1:7001"]
    assert peer["rtt_ms"] == 0.8 and peer["reconnects"] == 2
    assert peer["bytes_sent"] == 1234 and peer["disconnects"] == 1
    text = render(report)
    assert "net:" in text and "127.0.0.1:7001" in text
    # health_attribution tallies the same kinds
    att = health_attribution(path)
    assert att["net"] == {"net": 3, "gossip": 1, "flaps": 1}
