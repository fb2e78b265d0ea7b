"""Fleet-tier invariants (serving/fleet/): tenant isolation under flood, QoS
shed order, engine-kill -> lease-expiry -> re-route with zero lost accepted
requests, autoscaler hysteresis (no flap on oscillating load), fleet rollout
monotonicity (no engine ever serves a version older than one it already
served), and the bucket-helper edge cases.  Router/registry/autoscale logic
runs against protocol fakes (the fleet layer is deliberately jax-free); the
`serve`-marked tests drive REAL PolicyServer engines through the same seams
(`make fleet-smoke`)."""

import threading
import time

import numpy as np
import pytest

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.parallel.elastic import HeartbeatWriter
from rainbow_iqn_apex_tpu.serving.batcher import (
    ServeFuture,
    ServerClosed,
    ServerOverloaded,
    pick_bucket,
)
from rainbow_iqn_apex_tpu.serving.engine import fit_buckets
from rainbow_iqn_apex_tpu.serving.fleet import (
    Autoscaler,
    EngineRegistry,
    FleetEngine,
    FleetRollout,
    FrontRouter,
    ScalePolicy,
    TokenBucket,
    parse_qos_classes,
)

OBS = np.zeros((4, 4, 2), np.uint8)


class FakeTransport:
    """Protocol fake for an engine: a queue of ServeFutures the test fulfils
    (``pump``) or kills (``kill``) deterministically."""

    def __init__(self, lanes=1, capacity=64, version=1):
        self.lanes = lanes
        self.buckets = (4,)
        self.capacity = capacity
        self.queue = []
        self._alive = True
        self._version = version
        self.version_history = [version]
        self.lock = threading.Lock()

    def submit(self, obs):
        with self.lock:
            if not self._alive:
                raise ServerClosed("engine dead")
            if len(self.queue) >= self.capacity:
                raise ServerOverloaded("engine queue full")
            fut = ServeFuture(obs)
            self.queue.append(fut)
            return fut

    def pump(self):
        """Fulfil everything queued (skipping cancelled slots, like the
        real batcher)."""
        with self.lock:
            q, self.queue = self.queue, []
        for fut in q:
            if not fut.cancelled():
                fut.set_result(0, np.zeros(4))

    def kill(self):
        with self.lock:
            q, self.queue = self.queue, []
            self._alive = False
        for fut in q:
            fut.set_error(ServerClosed("engine killed"))

    def flap(self):
        """Sever the wire only: error everything queued like a dropped
        connection, but leave the engine process alive (still
        submittable) — the net-chaos corruption failure mode."""
        with self.lock:
            q, self.queue = self.queue, []
        for fut in q:
            fut.set_error(ServerClosed("connection reset"))

    def depth(self):
        with self.lock:
            return len(self.queue)

    def alive(self):
        return self._alive

    def version(self):
        return self._version

    def set_version(self, v):
        self._version = int(v)
        self.version_history.append(int(v))


class FakeEngine:
    """Rollout-protocol fake: adopt() with the FleetEngine monotonicity
    guard, transport liveness, a version history the monotonicity test
    audits."""

    def __init__(self, engine_id, version=0):
        self.engine_id = engine_id
        self.transport = FakeTransport(version=version)
        self.adopted_params = None

    def adopt(self, params, version):
        if (version <= self.transport.version()
                and self.transport.version() > 0):
            raise ValueError("backward adopt refused")
        self.adopted_params = params
        self.transport.set_version(version)
        return version


def two_engine_router(**kwargs):
    reg = EngineRegistry()
    t0, t1 = FakeTransport(), FakeTransport()
    reg.attach(0, t0)
    reg.attach(1, t1)
    router = FrontRouter(reg, **kwargs)
    return router, reg, t0, t1


# --------------------------------------------------------------- QoS parsing
def test_parse_qos_classes():
    classes = parse_qos_classes("gold:50:0.5,std:200:0.35,batch:1000:0.15")
    assert [c.name for c in classes] == ["gold", "std", "batch"]
    assert classes[0].priority == 0 and classes[2].priority == 2
    assert classes[1].deadline_ms == 200.0 and classes[1].share == 0.35
    with pytest.raises(ValueError):
        parse_qos_classes("gold:50")  # not name:deadline:share
    with pytest.raises(ValueError):
        parse_qos_classes("a:1:0.7,b:1:0.7")  # shares past 1.0
    with pytest.raises(ValueError):
        parse_qos_classes("a:1:0.2,a:2:0.2")  # duplicate names
    with pytest.raises(ValueError):
        parse_qos_classes("")


def test_token_bucket_rate_and_burst():
    t = [0.0]
    b = TokenBucket(rate=10.0, burst=2, clock=lambda: t[0])
    assert b.try_take() and b.try_take()
    assert not b.try_take()  # burst exhausted
    t[0] += 0.1  # one refill interval at 10/s
    assert b.try_take() and not b.try_take()
    # rate <= 0 disables
    assert all(TokenBucket(0.0, 1, clock=lambda: t[0]).try_take()
               for _ in range(100))


# ---------------------------------------------------------- tenant isolation
def test_flooding_tenant_cannot_starve_another():
    """Rate isolation: a tenant hammering past its token-bucket refill sheds
    with reason tenant_rate while the victim tenant's submissions are ALL
    admitted — the flood never consumes the victim's share."""
    t = [0.0]
    router, _, t0, t1 = two_engine_router(
        max_inflight=1000, tenant_rate=10.0, tenant_burst=5,
        clock=lambda: t[0])
    flood_shed = flood_ok = 0
    for _ in range(50):  # flood at infinite rate: only the burst is admitted
        try:
            router.submit(OBS, tenant="flood")
            flood_ok += 1
        except ServerOverloaded as e:
            assert e.reason == "tenant_rate"
            flood_shed += 1
    assert flood_ok == 5 and flood_shed == 45
    for _ in range(5):  # victim at the same instant: untouched
        router.submit(OBS, tenant="victim")
    stats = router.stats()
    assert stats["tenants"]["victim"]["shed"] == 0
    assert stats["tenants"]["victim"]["accepted"] == 5
    t0.pump(), t1.pump()


def test_qos_reservation_sheds_lowest_class_first():
    """Class isolation: with gold reserved half the inflight bound, a batch
    flood fills only its own cap plus unreserved headroom — gold requests
    are still admitted at full pressure, and batch is what sheds."""
    classes = parse_qos_classes("gold:10:0.5,batch:1000:0.5")
    router, _, t0, t1 = two_engine_router(
        qos_classes=classes, default_class="batch", max_inflight=20,
        tenant_rate=0.0)
    admitted_batch = 0
    batch_reasons = set()
    for _ in range(30):  # flood the LOW class far past the global bound
        try:
            router.submit(OBS, tenant="flood", qos="batch")
            admitted_batch += 1
        except ServerOverloaded as e:
            batch_reasons.add(e.reason)
    assert admitted_batch == 10  # its own cap: share 0.5 * 20
    assert batch_reasons == {"class_inflight"}
    # gold still has its whole reserved share available
    for _ in range(10):
        router.submit(OBS, tenant="vip", qos="gold")
    assert router.stats()["tenants"]["vip"]["shed"] == 0
    # and past its reservation gold sheds too (global bound holds)
    with pytest.raises(ServerOverloaded):
        router.submit(OBS, tenant="vip", qos="gold")
    assert router.inflight() == 20
    t0.pump(), t1.pump()


# ------------------------------------------------------- dispatch / re-route
def test_least_depth_dispatch_weighted_by_lanes():
    reg = EngineRegistry()
    narrow, wide = FakeTransport(lanes=1), FakeTransport(lanes=4)
    reg.attach(0, narrow)
    reg.attach(1, wide)
    router = FrontRouter(reg, max_inflight=100)
    for _ in range(10):
        router.submit(OBS, tenant="t")
    # wide engine (4 lanes) should absorb ~4x the narrow engine's share
    assert wide.depth() == 8 and narrow.depth() == 2
    narrow.pump(), wide.pump()


def test_engine_kill_reroutes_accepted_requests_zero_lost():
    """The core fleet invariant: engine death mid-flight loses ZERO accepted
    requests — its queued futures fail over to survivors and complete."""
    router, reg, t0, t1 = two_engine_router(max_inflight=100)
    futs = [router.submit(OBS, tenant="t") for _ in range(12)]
    assert t0.depth() + t1.depth() == 12
    t0.kill()  # errors its queued futures -> router re-dispatches to t1
    t1.pump()
    for fut in futs:
        fut.result(timeout=2)
    stats = router.stats()
    assert stats["lost"] == 0 and stats["completed"] == 12
    assert stats["rerouted"] == 6  # half the load had landed on t0
    # the observed death evicted the engine from routing immediately
    assert [h.engine_id for h in reg.routable()] == [1]


def test_reroute_parks_on_full_survivor_instead_of_losing():
    """Backpressure is not death: when the dead engine's requests find the
    survivor momentarily FULL, they park in the retry queue and land once
    its batcher drains — lost stays zero against a healthy fleet."""
    reg = EngineRegistry()
    doomed, survivor = FakeTransport(capacity=64), FakeTransport(capacity=2)
    reg.attach(0, doomed)
    reg.attach(1, survivor)
    router = FrontRouter(reg, max_inflight=100, reroute_window_s=30.0)
    # fill the survivor to its bound, then land the rest on the doomed one
    futs = []
    while survivor.depth() < 2:
        futs.append(router.submit(OBS, tenant="t"))
    queued = [router.submit(OBS, tenant="t") for _ in range(3)]
    assert doomed.depth() == len(queued) + len(futs) - 2
    doomed.kill()  # survivor is full: nothing re-dispatches yet
    assert router.stats()["lost"] == 0  # parked, NOT lost
    # drain in waves: each housekeeping sweep places what fits in the
    # survivor's freed capacity (2 slots), exactly like live operation
    deadline = time.monotonic() + 5
    while (any(not f.done() for f in futs + queued)
           and time.monotonic() < deadline):
        survivor.pump()
        router.housekeeping()
    survivor.pump()
    for fut in futs + queued:
        fut.result(timeout=2)
    stats = router.stats()
    assert stats["lost"] == 0
    assert stats["completed"] == len(futs) + len(queued)
    assert stats["rerouted"] >= 1


def test_fleet_wide_wire_flap_parks_and_recovers_zero_lost():
    """Injected corruption can sever the connection to EVERY engine within
    one request's lifetime (the net-chaos soak does exactly this).  With
    all engine processes still alive, the re-route must PARK — not declare
    the accepted request lost — and complete once the wires heal: loss is
    reserved for zero live engines or reroute-window expiry."""
    router, reg, t0, t1 = two_engine_router(max_inflight=100,
                                            reroute_window_s=30.0)
    fut = router.submit(OBS, tenant="t")
    owner, other = (t0, t1) if t0.depth() else (t1, t0)
    owner.flap()  # severs the wire -> the request re-dispatches to `other`
    other.flap()  # ... which severs too: both tried, both suspect
    assert not fut.done()  # parked, NOT lost
    assert router.stats()["lost"] == 0
    deadline = time.monotonic() + 5
    while not fut.done() and time.monotonic() < deadline:
        router.housekeeping()  # poll rehabilitates the live transports and
        t0.pump(), t1.pump()   # the retry queue clears `tried` to re-land
    fut.result(timeout=2)
    stats = router.stats()
    assert stats["lost"] == 0 and stats["completed"] == 1
    assert stats["rerouted"] >= 1


def test_submit_rejects_unknown_qos_class():
    router, _, t0, t1 = two_engine_router(
        qos_classes=parse_qos_classes("gold:10:0.5,std:100:0.5"),
        default_class="std", max_inflight=8)
    with pytest.raises(ValueError, match="glod"):
        router.submit(OBS, tenant="t", qos="glod")
    assert router.stats()["accepted"] == 0


def test_all_engines_dead_loses_inflight_and_sheds_new():
    router, reg, t0, t1 = two_engine_router(max_inflight=100)
    fut = router.submit(OBS, tenant="t")
    t0.kill(), t1.kill()
    with pytest.raises(ServerClosed):
        fut.result(timeout=2)
    assert router.stats()["lost"] == 1  # gated at zero in the soak
    reg.poll()
    with pytest.raises(ServerOverloaded) as ei:
        router.submit(OBS, tenant="t")
    assert ei.value.reason == "no_engine"


def test_routed_cancel_propagates_to_engine_future():
    router, _, t0, t1 = two_engine_router(max_inflight=100)
    fut = router.submit(OBS, tenant="t")
    assert fut.cancel()
    engine_fut = (t0.queue + t1.queue)[0]
    assert engine_fut.cancelled()  # the batch slot will be skipped
    t0.pump(), t1.pump()
    stats = router.stats()
    assert stats["cancelled"] == 1 and stats["lost"] == 0
    assert router.inflight() == 0


def test_weight_lag_fence_excludes_stale_engine():
    """An engine behind the rollout target by more than max_weight_lag is
    unroutable (StalenessFence semantics at the router): all traffic lands
    on the fresh engine until the straggler catches up."""
    reg = EngineRegistry()
    stale, fresh = FakeTransport(version=1), FakeTransport(version=4)
    reg.attach(0, stale)
    reg.attach(1, fresh)
    router = FrontRouter(reg, max_inflight=100, max_weight_lag=1,
                         target_version_fn=lambda: 4)
    for _ in range(6):
        router.submit(OBS, tenant="t")
    assert stale.depth() == 0 and fresh.depth() == 6
    stale.set_version(4)  # caught up: routable again
    for _ in range(4):
        router.submit(OBS, tenant="t")
    assert stale.depth() > 0
    stale.pump(), fresh.pump()


# ------------------------------------------------------------- lease registry
def test_registry_discovers_and_evicts_engines_via_leases(tmp_path):
    """Engine membership IS the PR-4 lease machinery: a fresh role=engine
    lease (with the lanes/buckets/queue_depth payload) surfaces the engine;
    a stale one evicts it on the same timeout that declares hosts dead."""
    hb = str(tmp_path / "hb")
    writer = HeartbeatWriter(hb, 7, interval_s=10.0, role="engine", epoch=2)
    writer.update_payload(lanes=4, buckets=[8, 16])
    writer.payload_fn = lambda: {"weight_version": 3, "queue_depth": 5}
    writer.beat()
    reg = EngineRegistry(hb, lease_timeout_s=0.5)
    events = reg.poll()
    assert events and events[0]["event"] == "engine_alive"
    assert events[0]["engine"] == 7 and events[0]["epoch"] == 2
    (handle,) = reg.handles()
    assert handle.lease.lanes == 4 and handle.lease.buckets == (8, 16)
    assert handle.lease.queue_depth == 5 and handle.version() == 3
    assert not handle.routable  # discovered, but no transport attached yet
    reg.attach(7, FakeTransport())
    assert [h.engine_id for h in reg.routable()] == [7]
    time.sleep(0.6)  # lease expires
    events = reg.poll()
    assert any(e["event"] == "engine_dead" and e["engine"] == 7
               for e in events)
    assert reg.routable() == []


def test_mark_dead_sticks_until_a_newer_beat(tmp_path):
    """A dispatch-observed death outranks the corpse's final lease file: the
    engine stays evicted while that lease is merely unexpired (its aborted
    queue reads depth 0 and would rank FIRST), and only a beat written
    AFTER the observation — a real revival — rehabilitates it."""
    hb = str(tmp_path / "hb")
    writer = HeartbeatWriter(hb, 3, interval_s=10.0, role="engine")
    writer.beat()
    reg = EngineRegistry(hb, lease_timeout_s=30.0)
    reg.attach(3, FakeTransport())
    reg.poll()
    assert [h.engine_id for h in reg.routable()] == [3]
    reg.mark_dead(3)
    reg.poll()  # the last lease is still fresh: must NOT resurrect
    assert reg.routable() == []
    time.sleep(0.05)
    writer.beat()  # a beat newer than the observation: genuinely back
    reg.poll()
    assert [h.engine_id for h in reg.routable()] == [3]


# ------------------------------------------------------ autoscaler hysteresis
def scripted_autoscaler(loads, policy=None, clock=None):
    engines = {"n": 2, "stopped": [], "spawned": []}

    def spawn(engine_id, epoch):
        engines["n"] += 1
        engines["spawned"].append(engine_id)
        return None

    def stop(engine_id):
        engines["n"] -= 1
        engines["stopped"].append(engine_id)

    it = iter(loads)
    scaler = Autoscaler(
        policy or ScalePolicy(min_engines=1, max_engines=4, up_depth=0.75,
                              down_depth=0.2, patience=3, cooldown_s=0.0),
        spawn_engine=spawn, stop_engine=stop,
        load_fn=lambda: next(it),
        clock=clock or time.monotonic,
    )
    scaler.adopt_engine(0)
    scaler.adopt_engine(1)
    return scaler, engines


def test_autoscaler_no_flap_on_oscillating_load():
    """Load oscillating across the scale-out threshold every evaluation can
    NEVER act: patience requires consecutive breaches, and the breach
    counter resets on every non-breach — zero actions over 40 sweeps."""
    loads = [{"depth_frac": 0.9 if i % 2 == 0 else 0.5, "p99_ms": None}
             for i in range(40)]
    scaler, engines = scripted_autoscaler(loads)
    actions = [scaler.evaluate() for _ in range(40)]
    assert all(a is None for a in actions)
    assert engines["spawned"] == [] and engines["stopped"] == []


def test_autoscaler_scales_out_on_sustained_load_then_cools_down():
    t = [0.0]
    loads = [{"depth_frac": 0.9, "p99_ms": None}] * 10
    scaler, engines = scripted_autoscaler(
        loads,
        policy=ScalePolicy(min_engines=1, max_engines=4, up_depth=0.75,
                           down_depth=0.2, patience=3, cooldown_s=100.0),
        clock=lambda: t[0])
    results = []
    for _ in range(10):
        results.append(scaler.evaluate())
        t[0] += 1.0
    acted = [r for r in results if r]
    # patience=3 -> the third consecutive breach acts; cooldown=100s then
    # blocks every later breach in this window: exactly ONE scale-out
    assert len(acted) == 1 and acted[0]["action"] == "out"
    assert results[2] is not None and engines["spawned"] == [2]


def test_autoscaler_scale_in_respects_floor():
    t = [0.0]
    loads = [{"depth_frac": 0.0, "p99_ms": None}] * 20
    scaler, engines = scripted_autoscaler(
        loads,
        policy=ScalePolicy(min_engines=1, max_engines=4, up_depth=0.75,
                           down_depth=0.2, patience=2, cooldown_s=0.0),
        clock=lambda: t[0])
    for _ in range(20):
        scaler.evaluate()
        t[0] += 1.0
    # 2 engines, floor 1: exactly one scale-in ever fires
    assert engines["stopped"] == [1] and len(scaler.engines()) == 1


# ------------------------------------------------------- rollout monotonicity
def test_rollout_is_monotone_and_refuses_backward():
    engines = [FakeEngine(i) for i in range(3)]
    rollout = FleetRollout()
    for e in engines:
        rollout.track(e)
    assert rollout.publish("w1", version=3)["event"] == "publish"
    assert rollout.publish("w2", version=7)["event"] == "publish"
    refused = rollout.publish("w_old", version=5)
    assert refused["event"] == "refused_backward" and rollout.refused == 1
    assert rollout.target_version == 7
    # implicit versioning continues ABOVE the refused attempt
    assert rollout.publish("w3")["version"] == 8
    for e in engines:
        hist = e.transport.version_history
        # the fleet invariant: no engine ever served a version older than
        # one it already served
        assert hist == sorted(hist)
        assert e.transport.version() == 8
    assert rollout.converged()


def test_rollout_sync_catches_up_late_joiner_and_converges():
    rollout = FleetRollout()
    early = FakeEngine(0)
    rollout.track(early)
    rollout.publish("w", version=2)
    late = FakeEngine(1)  # scale-out/respawn joins behind the target
    rollout.track(late)
    assert not rollout.converged() or late.transport.version() == 2
    assert rollout.sync() == 1
    assert late.transport.version() == 2 and rollout.converged()
    assert late.adopted_params == "w"
    # a dead engine never blocks convergence
    dead = FakeEngine(2)
    rollout.track(dead)
    dead.transport.kill()
    rollout.publish("w2")
    assert rollout.wait_converged(timeout_s=1.0)


def test_rollout_with_no_live_engine_is_not_converged():
    """An all-engines-down publish must not read as converged: convergence
    requires at least one LIVE engine actually serving the target."""
    rollout = FleetRollout()
    engine = FakeEngine(0)
    rollout.track(engine)
    engine.transport.kill()
    rollout.publish("w", version=1)
    assert not rollout.converged()
    assert rollout.maybe_emit_converged() is None
    assert not rollout.wait_converged(timeout_s=0.2)
    # ... until a live engine adopts it (the respawn path via sync)
    revived = FakeEngine(1)
    rollout.track(revived)
    rollout.sync()
    assert rollout.converged()


def test_autoscaler_cooldown_does_not_bank_breaches():
    """Breaches observed DURING cooldown (mid-warmup samples) must not count
    toward patience: the first post-cooldown evaluate cannot act — it takes
    `patience` fresh observations again."""
    t = [0.0]
    loads = [{"depth_frac": 0.9, "p99_ms": None}] * 30
    scaler, engines = scripted_autoscaler(
        loads,
        policy=ScalePolicy(min_engines=1, max_engines=5, up_depth=0.75,
                           down_depth=0.2, patience=3, cooldown_s=5.0),
        clock=lambda: t[0])
    actions = []
    for _ in range(16):
        actions.append(scaler.evaluate())
        t[0] += 1.0
    acted_at = [i for i, a in enumerate(actions) if a]
    # first action after 3 breaches (i=2); cooldown 5s ends at t=7 with
    # counters clean, so the second action needs 3 MORE breaches (i=9)
    assert acted_at == [2, 9]
    assert engines["spawned"] == [2, 3]


def test_fleet_engine_adopt_refuses_backward_locally():
    e = FakeEngine(0)
    e.adopt("w", 5)
    with pytest.raises(ValueError):
        e.adopt("w_old", 4)
    with pytest.raises(ValueError):
        e.adopt("w_dup", 5)
    assert e.transport.version() == 5


# ---------------------------------------------------------- bucket edge cases
def test_pick_bucket_edges():
    assert pick_bucket([8], 8) == 8  # n == max bucket, single-bucket list
    assert pick_bucket([8], 1) == 8
    assert pick_bucket([4, 8, 32], 32) == 32  # n == max bucket, multi
    assert pick_bucket([4, 8, 32], 9) == 32
    with pytest.raises(ValueError):
        pick_bucket([8], 9)


def test_fit_buckets_uneven_lanes():
    # lane counts that do NOT divide the requested buckets round UP to the
    # next multiple (and never below one full lane set)
    assert fit_buckets([10], 3) == [12]
    assert fit_buckets([3, 6], 4) == [4, 8]
    assert fit_buckets([5, 7], 6) == [6, 12]  # both round, dedupe keeps order
    assert fit_buckets([1], 8) == [8]
    assert fit_buckets([16], 16) == [16]  # n == lanes exactly


# --------------------------------------------------- obs rows + health folding
def test_fleet_row_kinds_validate_and_fold_into_health(tmp_path):
    """route/scale/rollout rows pass the obs schema, lint clean, and fold
    into RunHealth: router sheds degrade, a lost accepted request is a
    fault, a refused backward publish degrades the window, scale events are
    neutral sizing decisions."""
    import os
    import sys

    from rainbow_iqn_apex_tpu.obs.health import RunHealth
    from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry
    from rainbow_iqn_apex_tpu.obs.schema import validate_row
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts"))
    from lint_jsonl import lint_file

    path = str(tmp_path / "metrics.jsonl")
    logger = MetricsLogger(path, run_id="t", echo=False)
    reg = MetricRegistry()
    health = RunHealth(reg, logger=logger)
    logger.add_observer(health.observe_row)

    registry = EngineRegistry(logger=logger, obs_registry=reg)
    registry.attach(0, FakeTransport())
    router = FrontRouter(registry, max_inflight=8, logger=logger,
                         obs_registry=reg)
    router.submit(OBS, tenant="t")
    registry.get(0).transport.pump()
    router.emit_route_row()
    assert health.status() == "ok"  # traffic without sheds is healthy

    rollout = FleetRollout(logger=logger, obs_registry=reg)
    rollout.publish("w", version=1)
    assert rollout.publish("w_old", version=1)["event"] == "refused_backward"
    assert health.status() == "degraded"  # something tried to roll back
    health.tick(step=1)  # close the window

    logger.log("scale", action="out", engines=2, reason="depth")
    assert health.status() == "ok"  # a sizing decision is not a degradation
    assert reg.gauge("fleet_size", "health").get() == 2

    logger.log("route", accepted=10, shed=3, lost=1)
    assert health.status() == "degraded"
    assert health.total_shed == 3
    assert health.fault_counts["route_lost"] == 1
    row = health.tick(step=2)
    assert row["shed_total"] == 3

    logger.close()
    assert lint_file(path) == []
    import json as _json

    with open(path) as fh:
        rows = [_json.loads(line) for line in fh]
    assert {"route", "scale", "rollout", "health"} <= {r["kind"] for r in rows}
    for r in rows:
        assert validate_row(r) == [], r


def test_health_attribution_tallies_fleet_rows(tmp_path):
    """A run that drove a fleet (the bench soak) gets its route/scale/
    rollout activity attributed in the summary, like the heal tallies."""
    import json

    from rainbow_iqn_apex_tpu.obs.attribution import health_attribution

    run = tmp_path / "runs" / "r0"
    run.mkdir(parents=True)
    with open(run / "metrics.jsonl", "w") as f:
        f.write(json.dumps({"kind": "health", "status": "ok"}) + "\n")
        f.write(json.dumps({"kind": "route", "accepted": 9, "shed": 1}) + "\n")
        f.write(json.dumps({"kind": "route", "accepted": 4, "shed": 0}) + "\n")
        f.write(json.dumps({"kind": "scale", "action": "out",
                            "engines": 3}) + "\n")
        f.write(json.dumps({"kind": "rollout", "event": "publish",
                            "version": 2}) + "\n")
    attr = health_attribution(str(tmp_path / "runs" / "*" / "metrics.jsonl"))
    assert attr["fleet"] == {"route": 2, "scale": 1, "rollout": 1}
    assert attr["rows"] == 1  # health rows unaffected


# ------------------------------------------------- real engines (serve smoke)
CFG = Config(
    compute_dtype="float32",
    frame_height=44, frame_width=44, history_length=2,
    hidden_size=64, num_cosines=16,
    num_tau_samples=8, num_tau_prime_samples=8, num_quantile_samples=4,
    serve_batch_buckets="16",
    serve_deadline_ms=400.0,  # big coalescing window: requests stay QUEUED
    # long enough for the kill to catch them in flight, deterministically
    serve_queue_bound=64,
    fleet_lease_interval_s=0.05,
    fleet_lease_timeout_s=0.4,
)
A = 4


def _real_obs(n=1, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 255, (n, 44, 44, 2), dtype=np.uint8)


@pytest.fixture(scope="module")
def state():
    import jax

    from rainbow_iqn_apex_tpu.ops.learn import init_train_state

    return init_train_state(CFG, A, jax.random.PRNGKey(0))


def _real_fleet(state, tmp_path, n=2):
    import jax

    from rainbow_iqn_apex_tpu.serving import PolicyServer

    hb = str(tmp_path / "hb")
    reg = EngineRegistry(hb, lease_timeout_s=CFG.fleet_lease_timeout_s)
    rollout = FleetRollout()
    engines = []
    for i in range(n):
        server = PolicyServer(CFG, A, state.params,
                              devices=jax.devices()[:1])
        engine = FleetEngine(server, i, hb,
                             interval_s=CFG.fleet_lease_interval_s)
        engine.start(warmup=True)
        reg.attach(i, engine.transport)
        rollout.track(engine)
        engines.append(engine)
    router = FrontRouter(reg, max_inflight=128,
                         target_version_fn=rollout.version)
    return router, reg, rollout, engines


@pytest.mark.serve
def test_real_fleet_kill_reroute_and_rollout(state, tmp_path):
    """The `make fleet-smoke` pytest half on REAL engines: requests queued
    on a killed engine re-route and complete (zero lost), the lease expiry
    evicts the dead engine, and a fleet rollout converges with monotone
    versions throughout."""
    router, reg, rollout, engines = _real_fleet(state, tmp_path, n=2)
    try:
        rollout.publish(state.params, version=1)
        assert rollout.converged()
        # the 400ms coalescing deadline holds these below-bucket batches in
        # the queues while we kill engine 0 out from under its half
        futs = [router.submit(_real_obs(seed=i)[0], tenant="t")
                for i in range(8)]
        engines[0].kill()
        for fut in futs:
            action, q = fut.result(timeout=30)
            assert 0 <= action < A and q.shape == (A,)
        stats = router.stats()
        assert stats["lost"] == 0 and stats["completed"] == 8
        assert stats["accepted"] == 8
        # lease expiry confirms the death through the PR-4 monitor path
        deadline = time.monotonic() + 5
        dead_events = []
        while time.monotonic() < deadline and not dead_events:
            dead_events = [e for e in reg.poll()
                           if e["event"] == "engine_dead" and e["engine"] == 0]
            time.sleep(0.05)
        assert dead_events, "lease expiry never reported the killed engine"
        assert [h.engine_id for h in reg.routable()] == [1]
        # fleet rollout on the survivor: monotone, converged
        import jax

        perturbed = jax.tree.map(lambda x: x + 0.01, state.params)
        rollout.publish(perturbed, version=2)
        assert rollout.wait_converged(timeout_s=5.0)
        assert engines[1].transport.version() == 2
        assert rollout.publish(state.params, version=1)[
            "event"] == "refused_backward"
        # traffic still flows on the survivor, post-rollout
        assert 0 <= router.submit(_real_obs()[0], tenant="t").result(30)[0] < A
    finally:
        router.stop()
        for engine in engines:
            try:
                engine.stop()
            except Exception:
                pass


@pytest.mark.serve
def test_real_slow_client_cancel_frees_batch_capacity(state, tmp_path):
    """A slow client that times out and cancels must not burn a batch slot:
    the batcher skips the cancelled future (serve_cancelled_total) and live
    traffic keeps completing."""
    router, reg, rollout, engines = _real_fleet(state, tmp_path, n=1)
    try:
        fut = router.submit(_real_obs()[0], tenant="slow")
        with pytest.raises(TimeoutError):
            fut.result(timeout=0.01)
        assert fut.cancel()
        live = router.submit(_real_obs()[0], tenant="live")
        action, _ = live.result(timeout=30)
        assert 0 <= action < A
        stats = router.stats()
        assert stats["cancelled"] == 1 and stats["completed"] == 1
        total_cancelled = sum(
            e.server.metrics.total_cancelled for e in engines)
        assert total_cancelled == 1  # the batcher skipped the dead slot
    finally:
        router.stop()
        for engine in engines:
            engine.stop()
